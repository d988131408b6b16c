"""The mixers a ``LlamaConfig.mixer_types`` layer list may name beside the
block's own ``"attention"`` (and ``"none"``: a layer that is its
feed-forward part alone) — MiniCPM-SALA's two, Nemotron-H's Mamba-2,
DeepSeek-V2's latent attention, Brumby's power retention, Qwen3-Next's gated
delta rule and LFM2's gated short convolution:

- ``"lightning-attn"``: decayed linear attention (``ops.lightning_attention``)
  — per-head RMSNorm of q and k, RoPE, an RMSNorm over the concatenated
  heads, a sigmoid output gate.  Its per-sequence state is NOT pages: a
  fixed ``[heads, D, D]`` float32 state, one row of the pool's state array a
  live sequence (``kvcache.pool``); the paged programs are told which row
  each batch row continues (``state_rows``), and a call whose rows include
  position 0 starts from zeros.
- ``"minicpm4"``: InfLLM-V2 block-sparse softmax attention
  (``ops.block_select``) — per-head RMSNorm of q and k, NO positional
  encoding, a sigmoid output gate; K/V pages as every softmax layer plus a
  compressed-key cache, and attention over the pages the selection chose.
- ``"mamba2"``: the Mamba-2 selective scan (``ops.ssm_scan``) — one input
  projection to ``[z | x B C | dt]``, a causal depthwise convolution over
  ``x B C``, the scan with its data-dependent decay, a grouped RMSNorm gated
  by ``silu(z)``.  Its per-sequence state is a state row of TWO arrays: the
  float32 scan state ``[heads, P, N]`` and the convolution's last ``K - 1``
  inputs ``[K - 1, channels]`` in the activations' dtype.  A decode where
  the paged kernels run steps the scan state in ONE Pallas call on the array
  where it lies (``ops.ssm_scan.ssm_step``): the rows that are tokens by
  their ids, no byte of any other row, ``y`` of a skipped row exactly 0;
  elsewhere (the gather path, the CPU, a tp axis) the XLA step passes over
  every row it is handed.  A chunk slices its row out and writes it back.
- ``"mla"``: latent attention (DeepSeek-V2's MLA, ``ops.latent_attention``)
  — queries through a normed bottleneck, keys and values through ONE normed
  latent a token beside one RoPE key all heads share; the pool keeps pages
  of that row and nothing else, a decode attends them ABSORBED (the key
  up-projection folded into the query, the value's applied to the result)
  and a prefill chunk EXPANDED (keys and values up-projected a page inside
  the walk).

- ``"power-retention"``: power retention of degree 2 (Brumby's layer,
  ``ops.power_retention``) — Qwen3's attention block (grouped key/value
  heads, per-head RMSNorm of q and k, RoPE) with the softmax replaced by
  ``(q . k / sqrt d)^2`` under a per-token decay a key/value head,
  ``log_sigmoid(W_g h + b_g)``.  Its per-sequence state is a state row of
  TWO float32 arrays a layer: ``[kv heads, d, D]`` — the values against
  the symmetric square ``phi`` of the keys, ``D = 9,216`` at ``d = 128`` —
  and the normaliser ``[kv heads, d, d]``.  A decode steps the rows where
  they lie (``retention_step``, by row id); a chunk slices its row out and
  writes it back.  A model of such layers alone keeps NO page.
- ``"gated-delta"``: the gated delta rule (Qwen3-Next's linear-attention
  layers, ``ops.gated_delta``) — ``[q, k, v, z] = x W_qkvz`` and ``[b, a] =
  x W_ba`` laid out a KEY head (``gdn_key_heads`` of them; a key head serves
  ``gdn_value_heads / gdn_key_heads`` value heads), a causal depthwise
  convolution with ``silu`` over ``[q | k | v]``, ``beta = sigmoid(b)``,
  the log decay ``g = -exp(A_log) softplus(a + dt_bias)`` a value head, q
  and k L2-normalised a head (q scaled by ``Dk ** -0.5``), the rule ``S <-
  e^g S; S <- S + k (beta (v - S^T k))^T; o = S^T q``, then ``o <- w
  rms_norm(o) silu(z)`` a head (ONE plain weight ``[Dv]``) and the output
  projection.  Its per-sequence state is a state row of TWO arrays: the
  float32 state ``[value heads, Dk, Dv]`` and the convolution's last ``K -
  1`` inputs ``[K - 1, channels]`` in the activations' dtype.  Where the
  paged kernels run a decode steps the rows that are tokens where they lie
  (``gdn_step``, by row id, no byte of any other row), elsewhere as XLA
  operations on rows gathered and scattered; a chunk walks its blocks as
  XLA operations on its one row, sliced out and written back (scope
  ``gdn_chunk``: no kernel).  Scopes ``gdn_proj``,
  ``gdn_conv``, ``gdn_gates``, ``gdn_chunk``, ``gdn_step``, ``gdn_norm``.
- ``"conv"``: LFM2's gated short convolution — ``B, C, x = split3(in_proj
  h)``, a causal depthwise convolution of ``conv_L_cache`` taps over ``B *
  x`` with NO activation and no bias, ``out_proj(C * taps)``.  Scopes
  ``conv_in``, ``conv_gate``, ``conv_taps``, ``conv_out``.

Projections, norms and the cache protocol are the block's own
(``GQAQKVColumnParallelLinear``, ``RowParallelLinear``, ``RMSNorm``): a
mixer is called like ``LlamaAttention`` and returns ``(out, new cache)``.
What the rest of the package asks about a mixer is ONE record of
:data:`MIXER_KINDS`, at the end of this file.  ``"attention"`` trains (flash
kernels with their backward, every parallel layout) and serves; ``"conv"``
TRAINS and has no cached call (its gradients are tested against the float32
reference, ``tests/test_lfm2_moe.py``; its record says why it is not
served); the others SERVE and have no tested backward (their uncached call
differentiates as plain XLA operations, unmeasured and unchecked), and
tensor parallelism over their heads is not carried through
(``kvcache.pool.cache_plan`` refuses tp > 1).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
)
from neuronx_distributed_tpu.parallel.norm import RMSNorm
from neuronx_distributed_tpu.parallel.qkv import (
    GQAQKVColumnParallelLinear,
    Q_HEAD_AXES,
)

# the standard deviation a SEEDED embedding table of a layer-list model is
# drawn with: the MiniCPM family's ``initializer_range``.  With muP's 12 x
# embedding the table then leads the residual stream, as in a trained model;
# at flax's 0.02 the layers' additions outgrow it and a seeded network
# amplifies rounding (PERF.md, PR 29)
SEEDED_EMBED_STD = 0.1


class Launch(NamedTuple):
    """One paged program as the host knows it before it runs (what the
    serving engine hands :func:`launch_counters`): its queries' positions,
    each in its own row; the rows' lengths, one a query or one for all; the
    keys attended (a decode's contexts, a chunk's last row's); the rows
    computed (a decode's live slots, a chunk's own tokens); the rows the
    program is launched over (a decode's slots, a chunk's rows with its
    pads)."""

    family: str         # "decode_pages" | "prefill_chunk_pages"
    positions: Any
    lengths: Any
    visible: int
    rows: int
    width: int


def sparse_spec(cfg):
    from neuronx_distributed_tpu.ops.block_select import SparseSpec

    return SparseSpec(
        block_size=cfg.sparse_block_size, kernel_size=cfg.sparse_kernel_size,
        kernel_stride=cfg.sparse_kernel_stride,
        init_blocks=cfg.sparse_init_blocks,
        window_size=cfg.sparse_window_size, topk=cfg.sparse_topk,
        dense_len=cfg.sparse_dense_len)


def lightning_dims(cfg):
    """``(heads, head size)`` of the lightning layers."""
    return (cfg.lightning_heads or cfg.num_heads,
            cfg.lightning_head_dim or cfg.head_dim_)


def ssm_dims(cfg):
    """``(heads, head size P, groups G, state size N, convolution taps K)``
    of the Mamba-2 layers."""
    return (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state_size, cfg.ssm_conv_kernel)


def _lightning_state(cfg):
    nh, d = lightning_dims(cfg)
    return (((nh, d, d), "float32"),)


def _retention_state(cfg):
    from neuronx_distributed_tpu.ops.power_retention import phi_dim

    nkv, d = cfg.num_kv_heads, cfg.head_dim_
    return (((nkv, d, phi_dim(d)), "float32"), ((nkv, d, d), "float32"))


def gdn_dims(cfg):
    """``(key heads, value heads, Dk, Dv, convolution taps K)`` of the
    gated-delta layers."""
    return (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_head_dim,
            cfg.gdn_value_head_dim, cfg.gdn_conv_kernel)


def _gdn_state(cfg):
    hk, hv, dk, dv, k = gdn_dims(cfg)
    return (((hv, dk, dv), "float32"),
            ((k - 1, 2 * hk * dk + hv * dv), jnp.dtype(cfg.dtype).name))


def _ssm_state(cfg):
    nh, p, g, n, k = ssm_dims(cfg)
    return (((nh, p, n), "float32"),
            ((k - 1, nh * p + 2 * g * n), jnp.dtype(cfg.dtype).name))


def encode_positions(cfg, kind: str, q, k, positions):
    """The positional encoding of a mixer's q and k: RoPE on the lightning
    layers (``lightning_use_rope`` true), NONE on the block-sparse softmax
    layers (``attn_use_rope`` false) — the published choice, in one place."""
    if kind != "lightning-attn":
        return q, k
    from neuronx_distributed_tpu.models.llama import apply_rope, rope_sin_cos

    sin, cos = rope_sin_cos(positions, q.shape[-1], cfg.rope_theta,
                            cfg.rope_scaling_)
    return apply_rope(q, sin, cos), apply_rope(k, sin, cos)


class _GatedMixer(nn.Module):
    """What the two mixers share: q/k/v projections with a per-head RMSNorm
    of q and k, the sigmoid output gate, the output projection."""

    config: object

    def _qkv(self, x, heads, kv_heads, d):
        cfg = self.config
        q, k, v = GQAQKVColumnParallelLinear(
            num_heads=heads, num_kv_heads=kv_heads, head_dim=d,
            use_bias=cfg.qkv_bias, sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="qkv")(x)
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name=name)
        return norm("q_norm")(q), norm("k_norm")(k), v

    def _gate(self, x, width):
        """The sigmoid gate, float32: it multiplies the mixer's output
        before that is rounded to the activations' dtype, once."""
        cfg = self.config
        return jax.nn.sigmoid(ColumnParallelLinear(
            features=width, use_bias=False,
            sequence_parallel=cfg.sequence_parallel, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="gate")(x).astype(jnp.float32))

    def _out(self, y, gate):
        cfg = self.config
        y = (y.astype(jnp.float32) * gate).astype(cfg.dtype)
        return RowParallelLinear(
            features=cfg.hidden_size, use_bias=False,
            sequence_parallel=cfg.sequence_parallel,
            input_partition_axes=Q_HEAD_AXES, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o_proj")(y)


class LightningMixer(_GatedMixer):
    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_offset=0,
                 kv_valid=None, block_table=None, paged_kernel=False,
                 state_rows=None):
        from neuronx_distributed_tpu.models.llama import row_validity
        from neuronx_distributed_tpu.ops.lightning_attention import (
            lightning_attention,
        )

        cfg = self.config
        NH, D = lightning_dims(cfg)
        B, S = x.shape[0], x.shape[1]
        with jax.named_scope("lightning_attn"):
            q, k, v = self._qkv(x, NH, NH, D)
            q, k = encode_positions(cfg, "lightning-attn", q, k, positions)
            gate = self._gate(x, NH * D)
            live = row_validity(kv_valid, cache_offset, S,
                                kv_cache is not None)
            new_cache = None
            if kv_cache is None:
                state = jnp.zeros((B, NH, D, D), jnp.float32)
                o, _ = lightning_attention(q, k, v, live, state)
            else:
                if state_rows is None:
                    raise ValueError(
                        "a recurrent layer's cached call needs state_rows: "
                        "which row of the state array each batch row "
                        "continues")
                (states,) = kv_cache
                with jax.named_scope("state_read"):
                    state = states[state_rows]
                    # a call that holds position 0 begins its sequence
                    fresh = _fresh(positions, live)
                    state = jnp.where(fresh[:, None, None, None], 0.0, state)
                o, state = lightning_attention(q, k, v, live, state)
                with jax.named_scope("state_write"):
                    new_cache = (states.at[state_rows].set(state),)
                # the call's last row's k and v as the recurrence took them
                # (a decode's one token): with the state row before and
                # after, the step can be held to ``S' = lambda S + k^T v``
                self.sow("sparse_stats", "kv",
                         jnp.stack([k[:, -1], v[:, -1]], axis=1))
            o = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="out_norm")(
                o.reshape(B, S, NH * D))
            return self._out(o, gate), new_cache


class SparseMixer(_GatedMixer):
    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_offset=0,
                 kv_valid=None, block_table=None, paged_kernel=False,
                 state_rows=None):
        cfg = self.config
        NQ, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
        B, S = x.shape[0], x.shape[1]
        spec = sparse_spec(cfg)
        q, k, v = self._qkv(x, NQ, NKV, D)
        q, k = encode_positions(cfg, "minicpm4", q, k, positions)
        gate = self._gate(x, NQ * D)
        new_cache = None
        if kv_cache is None:
            out = _uncached_sparse(q, k, v, positions, kv_valid, spec)
        else:
            if block_table is None or jnp.ndim(cache_offset) != 1:
                raise ValueError(
                    "the minicpm4 mixer is served through the page pool "
                    "(block tables, per-slot offsets)")
            from neuronx_distributed_tpu.ops.block_select import (
                sparse_paged_attention,
            )

            out, new_cache, chosen = sparse_paged_attention(
                q, k, v, kv_cache, block_table, cache_offset, kv_valid,
                spec, paged_kernel)
            self.sow("sparse_stats", "chosen", chosen)
        return self._out(out.reshape(B, S, NQ * D), gate), new_cache


def _uncached_sparse(q, k, v, positions, kv_valid, spec):
    """A whole sequence at once, no cache (a forward pass for scoring or
    the tests): the same rule over blocks by POSITION, with the ``[S, S]``
    mask — short sequences only."""
    B, S, NQ, D = q.shape
    NKV = k.shape[2]
    G = NQ // NKV
    st, ks, bs = spec.kernel_stride, spec.kernel_size, spec.block_size
    live = (jnp.ones((B, S), bool) if kv_valid is None
            else jnp.asarray(kv_valid) > 0)
    # rows are left-padded: token with position p sits at row pad + p
    pad = jnp.argmax(live, axis=1)
    n_row = jnp.sum(live, axis=1)
    pos = positions
    NJ = max((S - ks) // st + 1, 1)
    NB = -(-S // bs)
    rows = pad[:, None, None] + st * jnp.arange(NJ)[None, :, None] \
        + jnp.arange(ks)[None, None, :]                      # [B, NJ, ks]
    kf = k.astype(jnp.float32)
    kbar = jnp.mean(kf[jnp.arange(B)[:, None, None],
                       jnp.clip(rows, 0, S - 1)], axis=2)    # [B, NJ, NKV, D]
    qg = q.reshape(B, S, NKV, G, D)
    lg = jnp.einsum("bskgd,bjkd->bkgsj", qg.astype(jnp.float32), kbar) \
        * D ** -0.5
    j = jnp.arange(NJ)
    vis = ((st * j + ks - 1)[None, None, :] <= pos[:, :, None]) \
        & ((pad[:, None] + st * j[None, :] + ks - 1 < S)[:, None, :])
    visb = vis[:, None, None]
    lg = jnp.where(visb, lg, -jnp.inf)
    m = jnp.max(lg, axis=-1, keepdims=True)
    e = jnp.where(visb, jnp.exp(lg - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    den = jnp.sum(e, axis=-1, keepdims=True)
    A = jnp.sum(e / jnp.where(den == 0.0, 1.0, den), axis=2)  # [B,NKV,S,NJ]
    b = jnp.arange(NB)
    over = ((st * j[None, :] + ks - 1 >= bs * b[:, None])
            & (st * j[None, :] <= bs * b[:, None] + bs - 1))
    Bs = jnp.max(jnp.where(over[None, None, None], A[:, :, :, None, :], 0.0),
                 axis=-1)                                     # [B,NKV,S,NB]
    qb = (pos // bs)[:, :, None]
    first_w = (jnp.maximum(pos - spec.window_size + 1, 0) // bs)[:, :, None]
    forced = (b < spec.init_blocks) | ((b >= first_w) & (b <= qb))
    visible = b <= qb
    Bs = jnp.where(forced[:, None], jnp.inf, Bs)
    Bs = jnp.where(visible[:, None], Bs, -jnp.inf)
    from neuronx_distributed_tpu.ops.block_select import choose_blocks

    chosen = choose_blocks(Bs, n_row, spec)                   # [B,NKV,S,NB]
    kpos = jnp.arange(S)[None, :] - pad[:, None]              # [B, T]
    blk = jnp.clip(kpos // bs, 0, NB - 1)
    picked = jnp.take_along_axis(
        chosen, jnp.broadcast_to(blk[:, None, None, :], (B, NKV, S, S)),
        axis=-1)
    mask = picked & (kpos[:, None, None, :] <= pos[:, None, :, None]) \
        & (kpos >= 0)[:, None, None, :]
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    s = jnp.where(mask[:, :, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v, preferred_element_type=q.dtype)
    return out.reshape(B, S, NQ, D)


def _fresh(positions, live):
    """Which batch rows hold position 0 in this call: they begin their
    sequence, whatever their state row held."""
    return jnp.any((positions == 0) & (live if live is not None else True),
                   axis=1)


def _mamba_dt_bias(cfg):
    """Mamba-2's own draw: ``dt`` log-uniform in ``[dt_min, dt_max]``,
    floored, stored as its inverse softplus."""
    lo, hi, floor = cfg.ssm_dt_min, cfg.ssm_dt_max, cfg.ssm_dt_floor

    def init(key, shape, dtype):
        import math

        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


def gated_group_norm(y, z, groups: int, eps: float):
    """Mamba-2's gated norm, float32: RMSNorm over each group's channels of
    ``y * silu(z)`` (``y, z [..., d_inner]``; ONE group is the whole
    width)."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return yg.reshape(y.shape)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 layer (module docstring).  What a cached call reads and
    writes of the layer's state arrays ``(scan state [R, NH, P, N] float32,
    taps [R, K - 1, channels])``: a chunk (``S > 1``) slices its one row out
    and writes it back; a decode (``S == 1``) where the paged kernels run
    (``paged_kernel`` resolved true, tp = 1) hands the WHOLE scan-state
    array to ``ops.ssm_scan.ssm_step``, which reads and writes the rows
    that are tokens (``row_validity``), by ``state_rows`` or their own
    index, and no byte of any other — such a row keeps its bits and its
    ``y`` is exactly 0, so what leaves the layer for it is the out
    projection of zeros; elsewhere the XLA step passes over every row it is
    handed (all of them when ``state_rows`` is None, a gather and a scatter
    of whole rows when it is given), an identity step for a row that is no
    token.  The taps are small and go the XLA way on every path."""

    config: object

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_offset=0,
                 kv_valid=None, block_table=None, paged_kernel=False,
                 state_rows=None):
        from neuronx_distributed_tpu.models.llama import row_validity
        from neuronx_distributed_tpu.ops.ssm_scan import (
            causal_conv,
            ssm_scan,
            ssm_step,
        )
        from neuronx_distributed_tpu.parallel.mesh import (
            get_tensor_parallel_size,
            model_parallel_is_initialized,
        )

        cfg = self.config
        NH, P, G, N, K = ssm_dims(cfg)
        d_inner, conv_ch = NH * P, NH * P + 2 * G * N
        B, S = x.shape[0], x.shape[1]
        f32 = jnp.float32
        small = lambda name, init, shape, dtype: jnp.asarray(self.param(  # noqa: E731
            name, nn.with_partitioning(init, (None,) * len(shape)), shape,
            dtype))
        from neuronx_distributed_tpu.parallel.moe import per_expert_lecun

        # (seeded weights are drawn in float32 and rounded: per_expert_lecun)
        proj = ColumnParallelLinear(
            features=2 * d_inner + 2 * G * N + NH, use_bias=False,
            sequence_parallel=cfg.sequence_parallel, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=per_expert_lecun,
            name="in_proj")(x)
        z, xbc, dt = jnp.split(proj, [d_inner, d_inner + conv_ch], axis=-1)
        bound = K ** -0.5       # torch's Conv1d draw at a fan-in of K
        uniform = lambda key, shape, dtype: jax.random.uniform(  # noqa: E731
            key, shape, f32, -bound, bound).astype(dtype)
        conv_w = small("conv_weight", uniform, (K, conv_ch), cfg.param_dtype)
        conv_b = small("conv_bias", uniform, (conv_ch,), cfg.param_dtype)
        # the scan's own scalars a head stay float32 whatever the weights are
        dt_bias = small("dt_bias", _mamba_dt_bias(cfg), (NH,), f32)
        A_log = small("A_log", lambda key, shape, dtype: jnp.log(
            jax.random.uniform(key, shape, dtype, 1.0, 16.0)), (NH,), f32)
        D = small("D", nn.initializers.ones, (NH,), f32)
        live = row_validity(kv_valid, cache_offset, S, kv_cache is not None)
        new_cache = None
        # a decode where the paged kernels run steps the state rows that are
        # tokens, where they lie and by id, in ONE Pallas call
        # (``ops.ssm_scan.ssm_step``): a row that is no token moves no byte.
        # Elsewhere — the gather path, the CPU, heads split over a tp axis
        # (a Mosaic call is not partitioned) — the XLA step below
        in_kernel = (
            paged_kernel and S == 1 and kv_cache is not None
            and not (model_parallel_is_initialized()
                     and get_tensor_parallel_size() > 1))
        if kv_cache is None:
            state = jnp.zeros((B, NH, P, N), f32)
            taps = jnp.zeros((B, K - 1, conv_ch), cfg.dtype)
        else:
            states, all_taps = kv_cache
            # ``state_rows`` None: batch row b continues state row b (a
            # decode of every slot).  The XLA step then runs on the arrays
            # where they lie: gathering 64 rows of 2 MiB at traced ids,
            # stepping and scattering them back took 1.62 ms a layer on the
            # v5e, the step on the array itself 0.51 (PERF.md, PR 32, step 0)
            whole = state_rows is None
            if whole and states.shape[0] != B:
                raise ValueError(
                    "a recurrent layer's cached call over fewer rows than "
                    "state rows needs state_rows: which row of the state "
                    "arrays each batch row continues")
            with jax.named_scope("state_read"):
                fresh = _fresh(positions, live)
                if not in_kernel:
                    state = jnp.where(
                        fresh[:, None, None, None], 0.0,
                        states if whole else states[state_rows])
                taps = jnp.where(fresh[:, None, None], 0,
                                 all_taps if whole else all_taps[state_rows])
        xbc, taps = causal_conv(xbc, taps, conv_w, conv_b, live)
        xs, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + G * N], axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        if in_kernel:
            y, state = ssm_step(
                states, xs.reshape(B, NH, P), Bm.reshape(B, G, N),
                Cm.reshape(B, G, N), dt[:, 0], -jnp.exp(A_log), D,
                None if live is None else live[:, 0], fresh, state_rows)
        else:
            y, state = ssm_scan(
                xs.reshape(B, S, NH, P), Bm.reshape(B, S, G, N),
                Cm.reshape(B, S, G, N), dt, -jnp.exp(A_log), D, live, state,
                cfg.ssm_chunk_rows)
        if kv_cache is not None:
            with jax.named_scope("state_write"):
                new_cache = (
                    state if whole or in_kernel
                    else states.at[state_rows].set(state),
                    taps if whole else all_taps.at[state_rows].set(taps))
        norm_w = small("norm_weight", nn.initializers.ones, (d_inner,),
                       cfg.param_dtype)
        y = (gated_group_norm(y.reshape(B, S, d_inner), z, G, cfg.rms_eps)
             * norm_w.astype(f32)).astype(cfg.dtype)
        return RowParallelLinear(
            features=cfg.hidden_size, use_bias=False,
            sequence_parallel=cfg.sequence_parallel,
            input_partition_axes=Q_HEAD_AXES, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=per_expert_lecun,
            name="out_proj")(y), new_cache


class GatedDeltaMixer(nn.Module):
    """The gated-delta layer (module docstring).  What a cached call reads
    and writes of the layer's state arrays ``(state [R, HV, Dk, Dv] float32,
    taps [R, K - 1, channels])``: the state goes to ``ops.gated_delta`` as
    the WHOLE array with the rows' ids (``state_rows``, or their own index)
    — a chunk's ``gdn_chunk`` slices its one row out and writes it back, a
    decode's ``gdn_step`` steps the live rows in place where the paged
    kernels run (``paged_kernel`` resolved true); the taps are small and go
    the XLA way (a gather and a scatter of rows) on every path."""

    config: object

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_offset=0,
                 kv_valid=None, block_table=None, paged_kernel=False,
                 state_rows=None):
        from neuronx_distributed_tpu.models.llama import row_validity
        from neuronx_distributed_tpu.ops import gated_delta as gd
        from neuronx_distributed_tpu.ops.ssm_scan import causal_conv
        from neuronx_distributed_tpu.parallel.moe import per_expert_lecun

        cfg = self.config
        HK, HV, Dk, Dv, K = gdn_dims(cfg)
        if not (HK and HV % HK == 0 and Dk and Dv):
            raise ValueError(
                "the gated-delta mixer needs gdn_key_heads, gdn_value_heads "
                "(a multiple of them), gdn_key_head_dim and "
                f"gdn_value_head_dim, got {gdn_dims(cfg)}")
        R = HV // HK
        key_w, val_w = HK * Dk, HV * Dv
        conv_ch = 2 * key_w + val_w
        B, S = x.shape[0], x.shape[1]
        f32 = jnp.float32
        small = lambda name, init, shape, dtype: jnp.asarray(self.param(  # noqa: E731
            name, nn.with_partitioning(init, (None,) * len(shape)), shape,
            dtype))
        # (seeded weights are drawn in float32 and rounded: per_expert_lecun)
        lin = dict(use_bias=False, sequence_parallel=cfg.sequence_parallel,
                   param_dtype=cfg.param_dtype, kernel_init=per_expert_lecun)
        with jax.named_scope("gdn_proj"):
            qkvz = ColumnParallelLinear(
                features=2 * key_w + 2 * val_w, dtype=cfg.dtype,
                name="in_proj_qkvz", **lin)(x)
            # the decay's and beta's logits leave their matmul in float32
            ba = ColumnParallelLinear(
                features=2 * HV, dtype=f32, name="in_proj_ba", **lin)(x)
            # a key head's (q Dk | k Dk | v R Dv | z R Dv) and (b R | a R)
            qkvz = qkvz.reshape(B, S, HK, 2 * Dk + 2 * R * Dv)
            q, k, v, z = jnp.split(
                qkvz, [Dk, 2 * Dk, 2 * Dk + R * Dv], axis=-1)
            ba = ba.reshape(B, S, HK, 2 * R)
            b, a = ba[..., :R].reshape(B, S, HV), ba[..., R:].reshape(B, S, HV)
            mixed = jnp.concatenate(
                [q.reshape(B, S, key_w), k.reshape(B, S, key_w),
                 v.reshape(B, S, val_w)], axis=-1)
            z = z.reshape(B, S, HV, Dv)
        bound = K ** -0.5       # torch's Conv1d draw at a fan-in of K
        conv_w = small("conv_weight", lambda key, shape, dtype:
                       jax.random.uniform(key, shape, f32, -bound, bound
                                          ).astype(dtype),
                       (K, conv_ch), cfg.param_dtype)
        # the rule's own scalars a head stay float32 whatever the weights
        # are; a SEEDED dt_bias is Mamba-2's draw (flash-linear-attention's:
        # the checkpoint's ones would give e^g near e^-10 a token for most
        # heads, and a state that forgets within a token carries nothing a
        # check could read)
        dt_bias = small("dt_bias", _mamba_dt_bias(cfg), (HV,), f32)
        A_log = small("A_log", lambda key, shape, dtype: jnp.log(
            jax.random.uniform(key, shape, dtype, 1e-3, 16.0)), (HV,), f32)
        live = row_validity(kv_valid, cache_offset, S, kv_cache is not None)
        new_cache = None
        if kv_cache is None:
            taps = jnp.zeros((B, K - 1, conv_ch), cfg.dtype)
        else:
            states, all_taps = kv_cache
            whole = state_rows is None
            if whole and states.shape[0] != B:
                raise ValueError(
                    "a recurrent layer's cached call over fewer rows than "
                    "state rows needs state_rows: which row of the state "
                    "arrays each batch row continues")
            with jax.named_scope("state_read"):
                fresh = _fresh(positions, live)
                taps = jnp.where(fresh[:, None, None], 0,
                                 all_taps if whole else all_taps[state_rows])
        mixed, taps = causal_conv(mixed, taps, conv_w, None, live,
                                  scope="gdn_conv")
        with jax.named_scope("gdn_gates"):
            q, k, v = jnp.split(mixed, [key_w, 2 * key_w], axis=-1)
            heads = lambda t, d: jnp.repeat(  # noqa: E731
                t.reshape(B, S, HK, d), R, axis=2)
            q = gd.l2_normalise(heads(q, Dk)) * Dk ** -0.5
            k = gd.l2_normalise(heads(k, Dk))
            v = v.reshape(B, S, HV, Dv)
            beta = jax.nn.sigmoid(b)
            g = -jnp.exp(A_log) * jax.nn.softplus(a + dt_bias)
        if kv_cache is None:
            o, _ = gd.gdn_scan(q, k, v, g, beta, live,
                               jnp.zeros((B, HV, Dk, Dv), f32))
        else:
            rows = (jnp.arange(B, dtype=jnp.int32) if whole
                    else jnp.asarray(state_rows, jnp.int32))
            if S == 1:
                o, states = gd.gdn_step(
                    states, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    None if live is None else live[:, 0], fresh, rows,
                    kernel=paged_kernel)
                o = o[:, None]
            else:
                o, states = gd.gdn_chunk(q, k, v, g, beta, live, fresh,
                                         states, rows)
            with jax.named_scope("state_write"):
                new_cache = (states, taps if whole
                             else all_taps.at[state_rows].set(taps))
        norm_w = small("norm_weight", nn.initializers.ones, (Dv,),
                       cfg.param_dtype)
        with jax.named_scope("gdn_norm"):
            # the published gated norm: the head's RMSNorm under its plain
            # weight, THEN silu(z) — float32, rounded once
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps)
            y = (o * norm_w.astype(f32) * jax.nn.silu(z.astype(f32))
                 ).astype(cfg.dtype).reshape(B, S, val_w)
        with jax.named_scope("gdn_proj"):
            return RowParallelLinear(
                features=cfg.hidden_size, dtype=cfg.dtype,
                input_partition_axes=Q_HEAD_AXES, name="o_proj", **lin)(
                y), new_cache


# a SEEDED decay bias is drawn so that a head's half-life lies between these
# many tokens, log-uniform: at a bias of 0 a seeded gate sits near 0.5, the
# state forgets in ten tokens and no check sees what a chunk hands the next
RETENTION_HALF_LIFE = (64.0, 8192.0)


def _retention_gate_bias(key, shape, dtype):
    import math

    lo, hi = RETENTION_HALF_LIFE
    half = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                   * (math.log(hi) - math.log(lo)) + math.log(lo))
    g = jnp.exp(-math.log(2.0) / half)          # the decay a token
    return (jnp.log(g) - jnp.log1p(-g)).astype(dtype)


def _log_decay(gate, bias):
    """A token's log decay a key/value head, float32, ``<= 0``."""
    return jax.nn.log_sigmoid(gate.astype(jnp.float32) + bias)


def phi(u, dtype=None):
    """The symmetric square of ``u [..., d]`` as the power-retention state
    lays it out (``ops.power_retention.phi``): ``phi(q) . phi(k) = (q .
    k)^2``."""
    from neuronx_distributed_tpu.ops.power_retention import phi as _phi

    return _phi(u, dtype)


class PowerRetentionMixer(nn.Module):
    config: object

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_offset=0,
                 kv_valid=None, block_table=None, paged_kernel=False,
                 state_rows=None):
        from neuronx_distributed_tpu.models.llama import (
            apply_rope,
            rope_sin_cos,
            row_validity,
        )
        from neuronx_distributed_tpu.ops import power_retention as pr

        cfg = self.config
        NQ, NKV, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
        B, S = x.shape[0], x.shape[1]
        f32 = jnp.float32
        with jax.named_scope("retention_proj"):
            q, k, v = GQAQKVColumnParallelLinear(
                num_heads=NQ, num_kv_heads=NKV, head_dim=d,
                use_bias=cfg.qkv_bias,
                sequence_parallel=cfg.sequence_parallel, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="qkv")(x)
            # the decay's logit leaves its matmul in float32: rounded to
            # bfloat16 at ~5-9 it would move a fast head's decay by 3%
            gate = ColumnParallelLinear(
                features=NKV, use_bias=False,
                sequence_parallel=cfg.sequence_parallel, dtype=jnp.float32,
                param_dtype=cfg.param_dtype, name="gate")(x)
        # the decay's own scalar a head stays float32 whatever the weights
        bias = jnp.asarray(self.param(
            "gate_bias", nn.with_partitioning(_retention_gate_bias, (None,)),
            (NKV,), f32))
        with jax.named_scope("retention_norm"):
            norm = lambda name: RMSNorm(  # noqa: E731
                eps=cfg.rms_eps, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name)
            sin, cos = rope_sin_cos(positions, d, cfg.rope_theta,
                                    cfg.rope_scaling_)
            q = apply_rope(norm("q_norm")(q), sin, cos)
            k = apply_rope(norm("k_norm")(k), sin, cos)
            lg = _log_decay(gate, bias)                        # [B, S, NKV]
        live = row_validity(kv_valid, cache_offset, S, kv_cache is not None)
        new_cache = None
        if kv_cache is None:
            o, _, _ = pr.power_retention(
                q, k, v, lg, live,
                jnp.zeros((B, NKV, d, pr.phi_dim(d)), f32),
                jnp.zeros((B, NKV, d, d), f32))
        else:
            if state_rows is None:
                raise ValueError(
                    "a recurrent layer's cached call needs state_rows: "
                    "which row of the state arrays each batch row continues")
            states, zs = kv_cache
            # a call that holds position 0 begins its sequence
            fresh = _fresh(positions, live)
            if S == 1:
                o, new_cache = self._step(q, k, v, lg, live, fresh, states,
                                          zs, state_rows, paged_kernel)
            else:
                o, states, zs = pr.retention_chunk(
                    q, k, v, lg, live, fresh, states, zs, state_rows,
                    kernel=paged_kernel)
                new_cache = (states, zs)
        with jax.named_scope("retention_proj"):
            return RowParallelLinear(
                features=cfg.hidden_size, use_bias=False,
                sequence_parallel=cfg.sequence_parallel,
                input_partition_axes=Q_HEAD_AXES, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="o_proj")(
                o.reshape(B, S, NQ * d)), new_cache

    @staticmethod
    def _step(q, k, v, lg, live, fresh, states, zs, state_rows, kernel):
        """One token a row (a decode): the state rows stepped where they
        lie, by row id; the small normaliser as a gather and a scatter."""
        from neuronx_distributed_tpu.ops import power_retention as pr

        B, _, NQ, d = q.shape
        NKV = k.shape[2]
        f32 = jnp.float32
        hi = jax.lax.Precision.HIGHEST
        with jax.named_scope("retention_step"):
            m = (jnp.ones((B,), f32) if live is None
                 else live[:, 0].astype(f32))
            qf = q[:, 0].astype(f32).reshape(B, NKV, NQ // NKV, d)
            kf, vf = k[:, 0].astype(f32), v[:, 0].astype(f32)
            g = jnp.exp(lg[:, 0] * m[:, None])                 # [B, NKV]
            keep = jnp.where(fresh[:, None], 0.0, g)
            z = zs[state_rows] * keep[..., None, None] \
                + (kf * m[:, None, None])[..., :, None] * kf[..., None, :]
            zs = zs.at[state_rows].set(z)
            den = jnp.einsum("bkgi,bkij,bkgj->bkg", qf, z, qf, precision=hi)
        states, num = pr.retention_step(
            states, state_rows, keep, kf * m[:, None, None], qf, vf,
            kernel=kernel)
        with jax.named_scope("retention_step"):
            o = pr._normalise(num, den, d)
        return o.reshape(B, 1, NQ, d).astype(q.dtype), (states, zs)


class ConvMixer(nn.Module):
    """LFM2's gated short convolution (module docstring).  The gates and
    the taps' sum are float32, each rounded once to the activations' dtype."""

    config: object

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_offset=0,
                 kv_valid=None, block_table=None, paged_kernel=False,
                 state_rows=None):
        from neuronx_distributed_tpu.ops.ssm_scan import causal_conv
        from neuronx_distributed_tpu.parallel.moe import per_expert_lecun

        if kv_cache is not None:
            raise ValueError(
                "the 'conv' mixer has no cached call: it trains, and the "
                "serving engine refuses it by name")
        cfg = self.config
        H, K = cfg.hidden_size, cfg.conv_L_cache
        f32 = jnp.float32
        # (seeded weights are drawn in float32 and rounded: per_expert_lecun)
        lin = dict(use_bias=False, sequence_parallel=cfg.sequence_parallel,
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                   kernel_init=per_expert_lecun)
        with jax.named_scope("conv_in"):
            bcx = ColumnParallelLinear(features=3 * H, name="in_proj",
                                       **lin)(x)
        bound = K ** -0.5       # torch's Conv1d draw at a fan-in of K
        w = jnp.asarray(self.param(
            "conv_weight", nn.with_partitioning(
                lambda key, shape, dtype: jax.random.uniform(
                    key, shape, f32, -bound, bound).astype(dtype),
                (None, None)), (K, H), cfg.param_dtype))
        with jax.named_scope("conv_gate"):
            b_gate, c_gate, u = jnp.split(bcx, 3, axis=-1)
            u = (b_gate.astype(f32) * u.astype(f32)).astype(cfg.dtype)
        live = None if kv_valid is None else jnp.asarray(kv_valid) > 0
        v, _ = causal_conv(
            u, jnp.zeros((x.shape[0], K - 1, H), cfg.dtype), w, None, live,
            silu=False, scope="conv_taps")
        with jax.named_scope("conv_gate"):
            y = (c_gate.astype(f32) * v.astype(f32)).astype(cfg.dtype)
        with jax.named_scope("conv_out"):
            return RowParallelLinear(
                features=H, name="out_proj", input_partition_axes=Q_HEAD_AXES,
                **lin)(y), None


def mla_softmax_scale(cfg) -> float:
    """``(dn + dr)^-1/2``, times YaRN's ``mscale(factor, mscale_all_dim)^2``
    where the config stretches its RoPE and sets that term."""
    from neuronx_distributed_tpu.models.llama import yarn_mscale

    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_yarn_factor > 1.0 and cfg.rope_yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_yarn_factor,
                             cfg.rope_yarn_mscale_all_dim) ** 2
    return scale


# a cached call of at least this many rows a slot attends EXPANDED (a
# prefill chunk), fewer ABSORBED (a decode): PERF.md, PR 36, step 0
MLA_EXPANDED_MIN_ROWS = 64


def count_latents(reg, chunk_tokens: int, launch: Launch) -> None:
    """What the coming program's latent layers read and write, a layer,
    from the host offsets: the latent rows its queries attend
    (``launch.visible``: ``serving/latent_tokens_read_total``, also by
    program family), those of them a chunk up-projects to keys and values
    (the expanded path expands what it reads, once a chunk; a decode,
    absorbed, none: ``serving/latent_tokens_expanded_total``) and the rows
    it commits (``kvcache/latent_rows_written_total``, also by family)."""
    for name, n in (("serving/latent_tokens_read_total", launch.visible),
                    ("kvcache/latent_rows_written_total", launch.rows)):
        reg.counter(name).inc(n)
        reg.counter(f"{name}/{launch.family}").inc(n)
    if launch.family == "prefill_chunk_pages" \
            and chunk_tokens >= MLA_EXPANDED_MIN_ROWS:
        reg.counter("serving/latent_tokens_expanded_total").inc(
            launch.visible)


def _latent_cells(cache_offset, block_table, kv_valid, rows, page, num_pages):
    """``(phys [B, rows], in_off)``: the pool cell of each new row, as the
    K/V write of ``models.llama`` finds it — row ``s`` of slot ``b`` is
    cache index ``cache_offset[b] + s``; a parked slot's, a row's past the
    table and a pad row's (validity 0) page is ``num_pages``: dropped."""
    PP = block_table.shape[1]
    T = PP * page
    idx = cache_offset[:, None] + jnp.arange(rows)[None, :]
    phys = jnp.take_along_axis(block_table,
                               jnp.clip(idx // page, 0, PP - 1), axis=1)
    phys = jnp.where(idx < T, phys, num_pages)
    if kv_valid is not None:
        live = jnp.take_along_axis(jnp.asarray(kv_valid),
                                   jnp.clip(idx, 0, T - 1), axis=1) > 0
        phys = jnp.where(live, phys, num_pages)
    return phys, idx % page


class MLAMixer(nn.Module):
    config: object

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_offset=0,
                 kv_valid=None, block_table=None, paged_kernel=False,
                 state_rows=None):
        from neuronx_distributed_tpu.models.llama import (
            _causal_mask,
            apply_rope,
            rope_sin_cos,
        )
        from neuronx_distributed_tpu.ops import latent_attention as la
        from neuronx_distributed_tpu.parallel.moe import per_expert_lecun

        cfg = self.config
        NH, rank = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        B, S = x.shape[0], x.shape[1]
        # (seeded weights are drawn in float32 and rounded: per_expert_lecun)
        lin = dict(use_bias=False, sequence_parallel=cfg.sequence_parallel,
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                   kernel_init=per_expert_lecun)
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name=name)
        with jax.named_scope("mla_q"):
            cq = norm("q_a_norm")(ColumnParallelLinear(
                features=cfg.q_lora_rank, name="q_a", **lin)(x))
            q = ColumnParallelLinear(features=NH * (dn + dr), name="q_b",
                                     **lin)(cq).reshape(B, S, NH, dn + dr)
        with jax.named_scope("mla_kv_down"):
            kva = ColumnParallelLinear(features=rank + dr, name="kv_a",
                                       **lin)(x)
            ckv = norm("kv_a_norm")(kva[..., :rank])
        sin, cos = rope_sin_cos(positions, dr, cfg.rope_theta,
                                cfg.rope_scaling_)
        q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], sin, cos)
        k_rope = apply_rope(kva[..., None, rank:], sin, cos)[:, :, 0]
        # the one up-projection of the latent, a head: [rank, NH, dn | dv]
        kv_b = jnp.asarray(self.param(
            "kv_b", nn.with_partitioning(
                lambda key, shape, dtype: per_expert_lecun(
                    key, (shape[0], shape[1] * shape[2]), dtype
                ).reshape(shape), (None, None, None)),
            (rank, NH, dn + dv), cfg.param_dtype)).astype(cfg.dtype)
        wk, wv = kv_b[..., :dn], kv_b[..., dn:]
        scale = mla_softmax_scale(cfg)
        new_cache = None
        if kv_cache is None:
            # a whole sequence, no cache: expanded, the [S, S] mask
            with jax.named_scope("mla_kv_up"):
                kn = jnp.einsum("btr,rhd->bthd", ckv, wk)
                v = jnp.einsum("btr,rhd->bthd", ckv, wv)
            s = (jnp.einsum("bshd,bthd->bhst", q_nope, kn,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bshd,btd->bhst", q_rope, k_rope,
                              preferred_element_type=jnp.float32)) * scale
            mask = _causal_mask(S, S, 0, None)[None, None]
            if kv_valid is not None:
                mask = jnp.logical_and(
                    mask, jnp.asarray(kv_valid)[:, None, None, :] > 0)
            s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            out = jnp.einsum("bhst,bthd->bshd", p, v,
                             preferred_element_type=q.dtype)
        else:
            if block_table is None or jnp.ndim(cache_offset) != 1:
                raise ValueError(
                    "the mla mixer is served through the page pool (block "
                    "tables, per-slot offsets)")
            (pool,) = kv_cache
            NP, page, R = pool.shape
            with jax.named_scope("latent_write"):
                from neuronx_distributed_tpu.ops.kv_pool_write import (
                    write_pool_rows,
                )

                row = jnp.concatenate(
                    [ckv, k_rope.astype(ckv.dtype),
                     jnp.zeros((B, S, R - rank - dr), ckv.dtype)], axis=-1)
                phys, in_off = _latent_cells(cache_offset, block_table,
                                             kv_valid, S, page, NP)
                # the K/V pool's writer, a latent row as one kv head's
                pool = write_pool_rows(pool[:, None], row[:, :, None], phys,
                                       in_off, kernel=paged_kernel)[:, 0]
            new_cache = (pool,)
            kv_start = (None if kv_valid is None else jnp.argmax(
                jnp.asarray(kv_valid) > 0, axis=1).astype(jnp.int32))
            attend = la.latent_attention if paged_kernel \
                else la.latent_attention_reference
            if S >= MLA_EXPANDED_MIN_ROWS:
                out = attend(
                    jnp.concatenate([q_nope, q_rope], axis=-1), pool,
                    block_table, cache_offset, kv_start, rank=rank,
                    sm_scale=scale, w_kv=(wk.transpose(1, 0, 2),
                                          wv.transpose(1, 0, 2)))
            else:
                with jax.named_scope("mla_absorb"):
                    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, wk)
                o_lat = attend(
                    jnp.concatenate([q_abs, q_rope], axis=-1), pool,
                    block_table, cache_offset, kv_start, rank=rank,
                    sm_scale=scale)
                with jax.named_scope("mla_absorb"):
                    out = jnp.einsum("bshr,rhd->bshd", o_lat, wv)
        return RowParallelLinear(
            features=cfg.hidden_size, name="o_proj",
            input_partition_axes=Q_HEAD_AXES, **lin)(
            out.reshape(B, S, NH * dv)), new_cache


@dataclasses.dataclass(frozen=True)
class MixerKind:
    """Everything the rest of the package asks about one mixer a layer list
    may name, the ONE place its name decides anything: what it keeps for a
    live sequence (``cache``, of ``kvcache.pool.CACHE_KINDS``); the flax
    ``module`` that builds it (None: the block's own attention, or no
    mixer); ``state``, ``cfg -> ((shape, dtype name), ...)``, the arrays of
    ONE layer's state of one sequence (recurrent kinds only); the stem its
    tokens are ``counted`` under by the program that ran them
    (``serving/<stem>_tokens_total/{chunk,step}``), the counter of the
    state rows its decodes have ``stepped`` and the one of the rows those
    decodes were launched over and did not step (``skipped``: slots that
    wait for a chunk's turn or hold no request — what a step that visits
    live rows alone does not move); whether a decode over every slot steps
    those rows where they lie (``rows_in_place``: the program is then told
    no rows); why a model with such layers is ``unserved`` (None: it has a
    cached call); and which of the block's switches it carries —
    ``partial_rotary`` (``LlamaConfig.partial_rotary_factor`` reaches every
    channel it rotates, or it rotates none) and ``zero_centered``
    (``norm_zero_centered`` reaches its norms, or it has none that store a
    weight about 1 where the switch wants one about 0).  A new kind carries
    neither until its record says so: a model that sets the switch beside
    it is refused (:func:`refuse_block_switches`)."""

    name: str
    cache: str
    module: Optional[type] = None
    state: Optional[Callable] = None
    counted: Optional[str] = None
    stepped: Optional[str] = None
    skipped: Optional[str] = None
    rows_in_place: bool = False
    unserved: Optional[str] = None
    partial_rotary: bool = False
    zero_centered: bool = False


MIXER_KINDS = {kind.name: kind for kind in (
    MixerKind("attention", "pages", partial_rotary=True, zero_centered=True),
    # (no RoPE on the block-sparse layers: encode_positions)
    MixerKind("minicpm4", "selected_pages", SparseMixer, partial_rotary=True),
    MixerKind("lightning-attn", "state", LightningMixer, _lightning_state),
    MixerKind("mamba2", "state", Mamba2Mixer, _ssm_state, counted="ssm",
              stepped="serving/ssm_state_rows_stepped_total",
              skipped="serving/ssm_state_rows_skipped_total",
              rows_in_place=True, partial_rotary=True),
    MixerKind("mla", "latent", MLAMixer),
    MixerKind("conv", "none", ConvMixer, unserved=(
        "the 'conv' mixer (LFM2's gated short convolution) has no cached "
        "call: a model with such layers trains and is not served "
        "(models/hybrid.py)"), partial_rotary=True),
    MixerKind("power-retention", "state", PowerRetentionMixer,
              _retention_state, counted="retention"),
    # (its one norm is published with a PLAIN weight beside the model's
    # zero-centred ones, and stored so)
    MixerKind("gated-delta", "state", GatedDeltaMixer, _gdn_state,
              counted="gdn",
              stepped="serving/gdn_state_rows_stepped_total",
              skipped="serving/gdn_state_rows_skipped_total",
              rows_in_place=True, partial_rotary=True, zero_centered=True),
    MixerKind("none", "none", partial_rotary=True, zero_centered=True),
)}
MIXERS = tuple(MIXER_KINDS)
CACHE_OF = {name: kind.cache for name, kind in MIXER_KINDS.items()}
# the recurrent kinds by name, as a refusal spells them
RECURRENT_NAMES = ", ".join(
    name for name, kind in MIXER_KINDS.items() if kind.state is not None)


def refuse_block_switches(cfg) -> None:
    """``LlamaConfig.partial_rotary_factor`` and ``norm_zero_centered`` are
    the block's and the ``"attention"`` mixer's: refused, in one sentence
    each, beside a mixer whose record does not carry them."""
    kinds = kinds_of(cfg)
    whole = [k.name for k in kinds if not k.partial_rotary]
    if cfg.partial_rotary_factor != 1.0 and whole:
        raise ValueError(
            "partial_rotary_factor turns a part of the 'attention' mixer's "
            f"heads: the {', '.join(whole)} mixers rotate the channels they "
            "always did")
    plain = [k.name for k in kinds if not k.zero_centered]
    if cfg.norm_zero_centered and plain:
        raise ValueError(
            "norm_zero_centered is the block's norms' and the 'attention' "
            f"mixer's: the other mixers' own norms ({plain}) store plain "
            "weights")


def kinds_of(cfg) -> tuple:
    """The records of the mixers ``cfg``'s layer list names, each once, in
    the table's order; () for a config without a layer list."""
    named = set(getattr(cfg, "mixer_types", None) or ())
    return tuple(k for k in MIXER_KINDS.values() if k.name in named)


def state_arrays(cfg, kind: str):
    """``((shape, dtype name), ...)``: the arrays of ONE recurrent layer's
    state of one sequence, for the mixer ``kind``."""
    return MIXER_KINDS[kind].state(cfg)


def hybrid_mixer(cfg, kind: str):
    module = MIXER_KINDS[kind].module if kind in MIXER_KINDS else None
    if module is None:
        raise ValueError(f"unknown mixer {kind!r} (known: {MIXERS})")
    return module(cfg, name="attn")


def _count_rows(reg, names: dict, skipped, launch: Launch) -> None:
    for name in names[launch.family]:
        reg.counter(name).inc(launch.rows)
    if skipped is not None and launch.family == "decode_pages":
        reg.counter(skipped).inc(launch.width - launch.rows)


def launch_counters(cfg, reg, chunk_tokens: int) -> tuple:
    """What the host counts into ``reg`` of each paged program of a model of
    ``cfg`` before it runs, one ``count(launch) -> span keys or None`` a
    kind that counts anything: the ``counted`` kinds' tokens by the program
    that runs them (a chunk's own tokens, a decode's live rows — which are
    the state rows it steps, where the kind counts those, beside the rows
    of its width it does not; the token counters are created here, at
    zero), the latent layers' rows, the selecting
    layers' blocks (whose ``selected_tokens`` goes on the launch's span).
    () without a layer list: such a model's launches build no
    :class:`Launch`."""
    counters = []
    for kind in kinds_of(cfg):
        if kind.counted is not None:
            chunk, step = (f"serving/{kind.counted}_tokens_total/{family}"
                           for family in ("chunk", "step"))
            reg.counter(chunk), reg.counter(step)
            counters.append(functools.partial(_count_rows, reg, {
                "prefill_chunk_pages": (chunk,),
                "decode_pages": (step,) + (
                    (kind.stepped,) if kind.stepped is not None else ())},
                kind.skipped))
    if getattr(cfg, "latent_layers", ()):
        counters.append(functools.partial(count_latents, reg, chunk_tokens))
    spec = getattr(cfg, "selection_spec", None)
    if spec is not None:
        from neuronx_distributed_tpu.ops.block_select import count_selection

        counters.append(functools.partial(count_selection, reg, spec))
    return tuple(counters)
