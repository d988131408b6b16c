"""GPT-NeoX model family, TPU-native.

Capability parity with the reference's GPT-NeoX 6.9B/20B TP+ZeRO-1 pretrain
port (``examples/training/tp_dp_gpt_neox_hf_pretrain/``), built from the
framework's GSPMD layer library rather than ported module-by-module.
Architecture follows HF ``GPTNeoXForCausalLM``: parallel residual
(``x + attn(ln1(x)) + mlp(ln2(x))``), partial rotary embeddings
(``rotary_pct`` of each head), LayerNorm with bias, biased linears, untied
embed-out head.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.models.common import (
    causal_lm_loss,  # noqa: F401 — shared loss, re-exported for this family
    dense_mha,
    maybe_remat,
)
from neuronx_distributed_tpu.models.llama import apply_rope, rope_sin_cos
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
    RowParallelLinear,
    shard_activation,
    trailing_spec,
)
from neuronx_distributed_tpu.parallel.mesh import SEQUENCE_AXES, TENSOR_AXES
from neuronx_distributed_tpu.parallel.norm import LayerNorm


@dataclasses.dataclass(frozen=True)
class GPTNeoXConfig:
    vocab_size: int = 50432
    hidden_size: int = 6144
    intermediate_size: int = 24576
    num_layers: int = 44
    num_heads: int = 64
    max_seq_len: int = 2048
    rotary_pct: float = 0.25
    rope_theta: float = 10000.0
    ln_eps: float = 1e-5
    use_parallel_residual: bool = True
    sequence_parallel: bool = True
    remat: str = "selective"  # none | selective | full
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def neox_20b(**overrides) -> "GPTNeoXConfig":
        """EleutherAI/gpt-neox-20b (reference 20B pretrain config,
        ``tp_dp_gpt_neox_20b_hf_pretrain.sh``)."""
        return GPTNeoXConfig(**overrides)

    @staticmethod
    def neox_6_9b(**overrides) -> "GPTNeoXConfig":
        return GPTNeoXConfig(**{**dict(
            hidden_size=4096, intermediate_size=16384, num_layers=32,
            num_heads=32), **overrides})

    @staticmethod
    def tiny(**overrides) -> "GPTNeoXConfig":
        return GPTNeoXConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=256,
            num_layers=2, num_heads=8, max_seq_len=128), **overrides})


def apply_partial_rope(x: jax.Array, positions: jax.Array, rotary_pct: float,
                       theta: float) -> jax.Array:
    """Rotate only the first ``rotary_pct`` of each head's dims (HF GPT-NeoX
    convention); the remainder passes through unrotated."""
    D = x.shape[-1]
    rot = int(D * rotary_pct)
    if rot == 0:
        return x
    sin, cos = rope_sin_cos(positions, rot, theta)
    return jnp.concatenate([apply_rope(x[..., :rot], sin, cos), x[..., rot:]], axis=-1)


class GPTNeoXAttention(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        B, S = x.shape[:2]
        N, D = cfg.num_heads, cfg.head_dim
        qkv = ColumnParallelLinear(
            features=3 * cfg.hidden_size,
            n_fused=3,
            use_bias=True,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="qkv",
        )(x)  # [B, S, 3, hidden]
        q, k, v = (qkv[..., i, :].reshape(B, S, N, D) for i in range(3))
        q = apply_partial_rope(q, positions, cfg.rotary_pct, cfg.rope_theta)
        k = apply_partial_rope(k, positions, cfg.rotary_pct, cfg.rope_theta)
        out = dense_mha(q, k, v, causal=True)
        out = out.reshape(B, S, cfg.hidden_size)
        return RowParallelLinear(
            features=cfg.hidden_size,
            use_bias=True,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="dense",
        )(out)


class GPTNeoXMLP(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = ColumnParallelLinear(
            features=cfg.intermediate_size,
            use_bias=True,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="dense_h_to_4h",
        )(x)
        h = jax.nn.gelu(h, approximate=False)  # HF-exact erf gelu (checkpoint parity)
        return RowParallelLinear(
            features=cfg.hidden_size,
            use_bias=True,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="dense_4h_to_h",
        )(h)


class GPTNeoXBlock(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        norm = lambda name: LayerNorm(eps=cfg.ln_eps, dtype=cfg.dtype,
                                      param_dtype=cfg.param_dtype, name=name)
        attn_out = GPTNeoXAttention(cfg, name="attn")(norm("ln_1")(x), positions)
        if cfg.use_parallel_residual:
            # x + attn(ln1(x)) + mlp(ln2(x)) — HF GPT-NeoX parallel residual
            mlp_out = GPTNeoXMLP(cfg, name="mlp")(norm("ln_2")(x))
            x = x + attn_out + mlp_out
        else:
            x = x + attn_out
            x = x + GPTNeoXMLP(cfg, name="mlp")(norm("ln_2")(x))
        if cfg.sequence_parallel:
            x = shard_activation(x, trailing_spec(x.ndim, seq=SEQUENCE_AXES, last=None))
        return x


class GPTNeoXForCausalLM(nn.Module):
    config: GPTNeoXConfig

    @nn.nowrap
    def build_pipelined(self, num_microbatches: int, schedule: str = "1f1b", seed: int = 0,
                        pipeline_cuts=None, num_chunks: int = 1):
        """Pipeline-capable-model protocol consumed by
        ``initialize_parallel_model`` when ``pipeline_parallel_size > 1``."""
        return build_pipelined_gpt_neox(
            self.config, num_microbatches=num_microbatches, seed=seed, schedule=schedule,
            pipeline_cuts=pipeline_cuts, num_chunks=num_chunks,
        )

    def setup(self):
        # setup-style (explicit names preserve the compact-era param paths)
        # so ``hidden``/``head`` below share submodules with ``__call__`` —
        # the chunked-loss-head protocol (models.common.make_causal_lm_loss_sum)
        cfg = self.config
        self.embed_in_mod = ParallelEmbedding(
            num_embeddings=cfg.vocab_size,
            features=cfg.hidden_size,
            sequence_parallel_output=cfg.sequence_parallel,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="embed_in",
        )
        block_cls = maybe_remat(GPTNeoXBlock, cfg.remat)
        self.blocks = [block_cls(cfg, name=f"layer_{i}")
                       for i in range(cfg.num_layers)]
        self.final_norm_mod = LayerNorm(
            eps=cfg.ln_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="final_norm")
        self.embed_out_mod = ColumnParallelLinear(
            features=cfg.vocab_size,
            use_bias=False,
            gather_output=False,  # vocab-sharded for parallel_cross_entropy
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="embed_out",
        )

    def __call__(self, ids, positions=None):
        return self.head(self.hidden(ids, positions))

    def hidden(self, ids, positions=None):
        """Backbone: final-norm hidden states with the sequence gathered
        back from SP (chunked-loss-head input)."""
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        h = self.embed_in_mod(ids)
        for blk in self.blocks:
            h = blk(h, positions)
        h = self.final_norm_mod(h)
        if cfg.sequence_parallel:
            h = shard_activation(h, trailing_spec(h.ndim, seq=None, last=None))
        return h

    def head(self, h):
        """Vocab-sharded logits for a (chunk of) hidden states."""
        return self.embed_out_mod(h)


class GPTNeoXHead(nn.Module):
    """Final norm + vocab-parallel out head, split out as the pipeline's head
    stage (mirrors ``llama.LlamaHead``)."""

    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        h = LayerNorm(eps=cfg.ln_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                      name="final_norm")(h)
        if cfg.sequence_parallel:
            h = shard_activation(h, trailing_spec(h.ndim, seq=None, last=None))
        return ColumnParallelLinear(
            features=cfg.vocab_size,
            use_bias=False,
            gather_output=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="embed_out",
        )(h)


def build_pipelined_gpt_neox(
    cfg: GPTNeoXConfig, num_microbatches: int, seed: int = 0, schedule: str = "1f1b",
    pipeline_cuts=None, num_chunks: int = 1,
):
    """Pipeline-parallel GPT-NeoX (the reference's 20B milestone topology,
    TP8 x PP4 1F1B); same engine protocol as
    ``llama.build_pipelined_llama``."""
    from neuronx_distributed_tpu.models.common import build_pipelined_causal_lm

    embed_mod = ParallelEmbedding(
        num_embeddings=cfg.vocab_size,
        features=cfg.hidden_size,
        sequence_parallel_output=cfg.sequence_parallel,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
    )
    block_mod = GPTNeoXBlock(cfg)
    head_mod = GPTNeoXHead(cfg)

    def block_fn(lp, x):
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return block_mod.apply({"params": lp}, x, positions)

    return build_pipelined_causal_lm(
        embed_mod=embed_mod,
        block_mod=block_mod,
        head_mod=head_mod,
        block_fn=block_fn,
        num_layers=cfg.num_layers,
        max_seq_len=cfg.max_seq_len,
        hidden_size=cfg.hidden_size,
        dtype=cfg.dtype,
        remat=cfg.remat,
        sequence_parallel=cfg.sequence_parallel,
        num_microbatches=num_microbatches,
        seed=seed,
        schedule=schedule,
        pipeline_cuts=pipeline_cuts,
        num_chunks=num_chunks,
    )
