"""Gemma family (Gemma-1 2B/7B) — tied-embedding decoder on the shared
Llama block stack.

Architecture deltas from Llama (all expressed as composition, no new
parallel primitives):

- **GeGLU MLP**: tanh-approximate gelu gate (``LlamaConfig.mlp_activation=
  "gelu_tanh"``) instead of SiLU;
- **embedding scaling**: hidden states scaled by ``sqrt(hidden_size)``
  after the embedding lookup (cast to the compute dtype, matching HF's
  ``normalizer`` exactly);
- **tied LM head**: logits come from ``ParallelEmbedding.attend`` — literal
  param reuse of the vocab-sharded table (the reference framework handles
  tying via shared-weight process groups, ``pipeline/partition.py:225-250``;
  here it is the same array);
- **(1 + w) RMSNorm convention**: HF Gemma computes ``x * (1 + weight)``;
  the converter folds the ``+1`` into the stored weight so the framework's
  standard :class:`~..parallel.norm.RMSNorm` is bit-equivalent;
- ``head_dim`` decoupled from ``hidden_size / num_heads`` (256 at both
  scales) — already first-class in the block stack.

The KV-cache protocol matches :class:`~.llama.LlamaForCausalLM`
(``apply(params, ids, positions, caches, offset, kv_valid=...)``), so the
serving engine (:mod:`~..trace.engine`) drives Gemma unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.models.common import maybe_remat
from neuronx_distributed_tpu.models.llama import (
    LlamaAttention,
    LlamaBlock,
    LlamaConfig,
    LlamaMLP,
)
from neuronx_distributed_tpu.parallel.layers import (
    ParallelEmbedding,
    shard_activation,
    trailing_spec,
)
from neuronx_distributed_tpu.parallel.mesh import SEQUENCE_AXES
from neuronx_distributed_tpu.parallel.norm import RMSNorm


@dataclasses.dataclass(frozen=True)
class GemmaConfig:
    vocab_size: int = 256000
    hidden_size: int = 3072
    intermediate_size: int = 24576
    num_layers: int = 28
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 256
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    sequence_parallel: bool = True
    remat: str = "selective"
    attention_impl: str = "dense"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def head_dim_(self) -> int:
        """Protocol-compat with LlamaConfig (the serving engine reads it)."""
        return self.head_dim

    def block_config(self) -> LlamaConfig:
        """The shared decoder-block config (GeGLU selected here)."""
        return LlamaConfig(
            vocab_size=self.vocab_size,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_layers=self.num_layers,
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            max_seq_len=self.max_seq_len,
            rope_theta=self.rope_theta,
            rms_eps=self.rms_eps,
            sequence_parallel=self.sequence_parallel,
            remat=self.remat,
            attention_impl=self.attention_impl,
            mlp_activation="gelu_tanh",
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )

    @staticmethod
    def gemma_2b(**overrides) -> "GemmaConfig":
        """Gemma-2B: MQA (1 kv head), head_dim 256."""
        return GemmaConfig(**{**dict(
            hidden_size=2048, intermediate_size=16384, num_layers=18,
            num_heads=8, num_kv_heads=1), **overrides})

    @staticmethod
    def gemma_7b(**overrides) -> "GemmaConfig":
        return GemmaConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "GemmaConfig":
        return GemmaConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=8, num_kv_heads=2, head_dim=16,
            max_seq_len=128), **overrides})


class GemmaForCausalLM(nn.Module):
    """Tied-embedding causal LM over the shared block stack.

    setup-style so :meth:`hidden` / :meth:`head` (the chunked-loss-head
    protocol, ``models.common.make_causal_lm_loss_sum``) can reuse the same
    tied table the forward uses; the list attribute ``layer`` reproduces the
    ``layer_i`` param paths the converter writes."""

    config: GemmaConfig

    def setup(self):
        cfg = self.config
        bcfg = cfg.block_config()
        self.embed = ParallelEmbedding(
            num_embeddings=cfg.vocab_size,
            features=cfg.hidden_size,
            # SP entry constraint applied per-phase in backbone (decode
            # keeps the sequence unsharded)
            sequence_parallel_output=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        # nn.remat forward cost is zero without a grad, so one wrapped class
        # serves both the train and cached-decode paths; paged_kernel (arg 9,
        # module = arg 0) is a python-static branch flag — remat must not
        # abstract it into a tracer
        block_cls = maybe_remat(LlamaBlock, cfg.remat, static_argnums=(9,))
        self.layer = [block_cls(bcfg) for _ in range(cfg.num_layers)]
        self.final_norm = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)

    @nn.nowrap  # no scope of its own, as LlamaForCausalLM.backbone
    def backbone(self, ids, positions=None, kv_caches=None, cache_offset=0,
                 kv_valid=None, segment_ids=None, block_table=None,
                 adapters=None, paged_kernel=False):
        """Everything of :meth:`__call__` but the head: ``(final-norm hidden
        states [B, S, H], new caches)`` (``LlamaForCausalLM.backbone``)."""
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        h = self.embed(ids)
        if cfg.sequence_parallel and kv_caches is None:
            h = shard_activation(
                h, trailing_spec(h.ndim, seq=SEQUENCE_AXES, last=None))
        # HF Gemma: hidden *= tensor(sqrt(H), dtype=hidden.dtype) — the cast
        # happens BEFORE the multiply, so match it exactly
        h = h * jnp.asarray(cfg.hidden_size ** 0.5, h.dtype)
        new_caches = []
        for i, block in enumerate(self.layer):
            cache = kv_caches[i] if kv_caches is not None else None
            h, c = block(h, positions, cache,
                         cache_offset if kv_caches is not None else 0,
                         kv_valid, segment_ids, block_table,
                         adapters[i] if adapters is not None else None,
                         paged_kernel)
            new_caches.append(c)
        h = self.final_norm(h)
        if cfg.sequence_parallel and kv_caches is None:
            # gather the sequence back before the tied head matmul
            h = shard_activation(h, trailing_spec(h.ndim, seq=None, last=None))
        return h, new_caches

    def __call__(self, ids, positions=None, kv_caches=None, cache_offset=0,
                 kv_valid=None, segment_ids=None, block_table=None,
                 adapters=None, paged_kernel=False):
        h, new_caches = self.backbone(
            ids, positions, kv_caches, cache_offset, kv_valid, segment_ids,
            block_table, adapters, paged_kernel)
        logits = self.embed.attend(h)
        return (logits, new_caches) if kv_caches is not None else logits

    def hidden(self, ids, positions=None, kv_valid=None, segment_ids=None):
        """Backbone only: final-norm hidden states ``[B, S, H]`` — the input
        the chunked loss head consumes."""
        h, _ = self.backbone(ids, positions, None, 0, kv_valid, segment_ids)
        return h

    def head(self, h):
        """Vocab-sharded logits for a (chunk of) hidden states via the tied
        table."""
        return self.embed.attend(h)


# ---------------------------------------------------------------------------
# Gemma-2: hybrid local/global attention, softcapped logits, sandwich norms
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Gemma2Config:
    """Gemma-2 (2B/9B/27B): the Gemma recipe plus

    - **hybrid attention**: even layers use a 4096-token sliding window,
      odd layers are global (HF ``layer_types`` alternation);
    - **logit softcapping**: attention scores pass ``50·tanh(s/50)``
      in-kernel (``ops.flash_attention`` ``softcap``), final logits
      ``30·tanh(s/30)``;
    - **sandwich norms**: RMSNorm before AND after each sublayer
      (input/post-attention, pre/post-feedforward);
    - **decoupled attention scale**: ``query_pre_attn_scalar ** -0.5``
      (equals head_dim for 2B/9B, differs on 27B).
    """

    vocab_size: int = 256000
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_layers: int = 26
    num_heads: int = 8
    num_kv_heads: int = 4
    head_dim: int = 256
    query_pre_attn_scalar: float = 256.0
    attn_softcap: float = 50.0
    final_softcap: float = 30.0
    sliding_window: int = 4096
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    sequence_parallel: bool = True
    remat: str = "selective"
    attention_impl: str = "dense"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def head_dim_(self) -> int:
        return self.head_dim

    def block_config(self, sliding: bool) -> LlamaConfig:
        """Block config for one layer; ``sliding`` selects the local-window
        variant (even layers in HF's ``layer_types`` alternation)."""
        return LlamaConfig(
            vocab_size=self.vocab_size,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_layers=self.num_layers,
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim,
            max_seq_len=self.max_seq_len,
            rope_theta=self.rope_theta,
            rms_eps=self.rms_eps,
            sequence_parallel=self.sequence_parallel,
            remat=self.remat,
            attention_impl=self.attention_impl,
            mlp_activation="gelu_tanh",
            sliding_window=self.sliding_window if sliding else None,
            attn_softcap=self.attn_softcap,
            attn_scale=self.query_pre_attn_scalar ** -0.5,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )

    @staticmethod
    def gemma2_2b(**overrides) -> "Gemma2Config":
        return Gemma2Config(**overrides)

    @staticmethod
    def gemma2_9b(**overrides) -> "Gemma2Config":
        return Gemma2Config(**{**dict(
            hidden_size=3584, intermediate_size=14336, num_layers=42,
            num_heads=16, num_kv_heads=8), **overrides})

    @staticmethod
    def gemma2_27b(**overrides) -> "Gemma2Config":
        # the one scale where the attention scale decouples from head_dim
        return Gemma2Config(**{**dict(
            hidden_size=4608, intermediate_size=36864, num_layers=46,
            num_heads=32, num_kv_heads=16, head_dim=128,
            query_pre_attn_scalar=144.0), **overrides})

    @staticmethod
    def tiny(**overrides) -> "Gemma2Config":
        return Gemma2Config(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=8, num_kv_heads=2, head_dim=16,
            query_pre_attn_scalar=16.0, sliding_window=16,
            max_seq_len=128), **overrides})


class Gemma2Block(nn.Module):
    """Sandwich-norm decoder block (HF ``Gemma2DecoderLayer.forward``):
    ``x + post_norm(attn(in_norm(x)))`` then
    ``x + post_ffw_norm(mlp(pre_ffw_norm(x)))`` — reusing the shared
    attention/MLP modules; the block config carries the per-layer window."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_offset=0,
                 kv_valid=None, segment_ids=None, block_table=None,
                 adapter=None, paged_kernel=False):
        cfg = self.config

        def norm(name):
            return RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name=name)

        h, new_cache = LlamaAttention(cfg, name="attn")(
            norm("input_norm")(x), positions, kv_cache, cache_offset,
            kv_valid, segment_ids, block_table, adapter, paged_kernel)
        x = x + norm("post_attn_norm")(h)
        h = LlamaMLP(cfg, name="mlp")(norm("pre_ffw_norm")(x))
        x = x + norm("post_ffw_norm")(h)
        if cfg.sequence_parallel:
            from neuronx_distributed_tpu.parallel.mesh import SEQUENCE_AXES as _SEQ

            x = shard_activation(x, trailing_spec(x.ndim, seq=_SEQ, last=None))
        return x, new_cache


class Gemma2ForCausalLM(nn.Module):
    """Tied-embedding Gemma-2 causal LM with hybrid local/global layers and
    softcapped final logits; same serving/chunked-loss protocols as
    :class:`GemmaForCausalLM`."""

    config: Gemma2Config

    def setup(self):
        cfg = self.config
        self.embed = ParallelEmbedding(
            num_embeddings=cfg.vocab_size,
            features=cfg.hidden_size,
            sequence_parallel_output=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        # HF layer_types alternation: even layers sliding, odd global;
        # paged_kernel (arg 9) stays python-static through remat
        self.layer = [
            maybe_remat(Gemma2Block, cfg.remat,
                        static_argnums=(9,))(cfg.block_config(i % 2 == 0))
            for i in range(cfg.num_layers)
        ]
        self.final_norm = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                                  param_dtype=cfg.param_dtype)

    @nn.nowrap  # no scope of its own, as LlamaForCausalLM.backbone
    def backbone(self, ids, positions=None, kv_caches=None, cache_offset=0,
                 kv_valid=None, segment_ids=None, block_table=None,
                 adapters=None, paged_kernel=False):
        """Everything of :meth:`__call__` but the head: ``(final-norm hidden
        states [B, S, H], new caches)`` (``LlamaForCausalLM.backbone``)."""
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        h = self.embed(ids)
        if cfg.sequence_parallel and kv_caches is None:
            h = shard_activation(
                h, trailing_spec(h.ndim, seq=SEQUENCE_AXES, last=None))
        h = h * jnp.asarray(cfg.hidden_size ** 0.5, h.dtype)
        new_caches = []
        for i, block in enumerate(self.layer):
            cache = kv_caches[i] if kv_caches is not None else None
            h, c = block(h, positions, cache,
                         cache_offset if kv_caches is not None else 0,
                         kv_valid, segment_ids, block_table,
                         adapters[i] if adapters is not None else None,
                         paged_kernel)
            new_caches.append(c)
        h = self.final_norm(h)
        if cfg.sequence_parallel and kv_caches is None:
            h = shard_activation(h, trailing_spec(h.ndim, seq=None, last=None))
        return h, new_caches

    def _logits(self, h):
        logits = self.embed.attend(h)
        cap = self.config.final_softcap
        if cap:
            logits = (cap * jnp.tanh(logits.astype(jnp.float32) / cap)).astype(
                logits.dtype)
        return logits

    def __call__(self, ids, positions=None, kv_caches=None, cache_offset=0,
                 kv_valid=None, segment_ids=None, block_table=None,
                 adapters=None, paged_kernel=False):
        h, new_caches = self.backbone(
            ids, positions, kv_caches, cache_offset, kv_valid, segment_ids,
            block_table, adapters, paged_kernel)
        logits = self._logits(h)
        return (logits, new_caches) if kv_caches is not None else logits

    def hidden(self, ids, positions=None, kv_valid=None, segment_ids=None):
        h, _ = self.backbone(ids, positions, None, 0, kv_valid, segment_ids)
        return h

    def head(self, h):
        return self._logits(h)
