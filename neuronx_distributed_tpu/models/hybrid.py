"""The mixers a ``LlamaConfig.mixer_types`` layer list may name beside the
block's own ``"attention"`` — MiniCPM-SALA's two:

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

Projections, norms and the cache protocol are the block's own
(``GQAQKVColumnParallelLinear``, ``RowParallelLinear``, ``RMSNorm``): a
mixer is called like ``LlamaAttention`` and returns ``(out, new cache)``.
Serving only: neither has a backward pass here, and tensor parallelism over
their heads is not carried through (the engine refuses tp > 1).
"""

from __future__ import annotations

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

MIXERS = ("attention", "minicpm4", "lightning-attn")
# what each mixer keeps for a live sequence, in the page pool's terms
# (``kvcache.pool.CACHE_KINDS``): the one place a mixer's name decides it —
# ``LlamaConfig.layer_caches`` hands it on, and the pool and the engines
# read the config
CACHE_OF = {"attention": "pages", "minicpm4": "selected_pages",
            "lightning-attn": "state"}
# the standard deviation a SEEDED embedding table of a layer-list model is
# drawn with: the MiniCPM family's ``initializer_range``.  With muP's 12 x
# embedding the table then leads the residual stream, as in a trained model;
# at flax's 0.02 the layers' additions outgrow it and a seeded network
# amplifies rounding (PERF.md, PR 29)
SEEDED_EMBED_STD = 0.1


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
                    fresh = jnp.any((positions == 0) & (
                        live if live is not None else True), axis=1)
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


def hybrid_mixer(cfg, kind: str):
    if kind == "lightning-attn":
        return LightningMixer(cfg, name="attn")
    if kind == "minicpm4":
        return SparseMixer(cfg, name="attn")
    raise ValueError(f"unknown mixer {kind!r} (known: {MIXERS})")
