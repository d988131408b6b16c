"""Normalization layers (reference ``parallel_layers/layer_norm.py`` and the
RMSNorm in ``modeling_llama_nxd.py:80-95``).

Computation runs in fp32 regardless of input dtype — the explicit-dtype
replacement for the reference's ``XLA_DOWNCAST_BF16`` double-trick
(``modeling_llama_nxd.py:125``).  In SP regions the input is sequence-sharded
and the op is purely elementwise over the hidden dim, so no collective is
needed; weight gradients are psum'd across TP by autodiff/GSPMD — the
reference needs a separate ``allreduce_sequence_parallel_gradients`` pass
(``grads.py:249-264``) only because its LN weights live outside autograd's
view of the TP group."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # the weight is stored ZERO-CENTRED: ``x_hat (1 + w)``, drawn at zeros
    # (Gemma's and Qwen3-Next's checkpoints; the sum is float32)
    zero_centered: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        init = (nn.initializers.zeros_init() if self.zero_centered
                else nn.initializers.ones_init())
        weight = self.param("weight", init, (x.shape[-1],), self.param_dtype)
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        scale = weight.astype(jnp.float32)
        if self.zero_centered:
            scale = 1.0 + scale
        return (y * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    eps: float = 1e-5
    use_bias: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dim = x.shape[-1]
        weight = self.param("weight", nn.initializers.ones_init(), (dim,), self.param_dtype)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * weight.astype(jnp.float32)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros_init(), (dim,), self.param_dtype)
            y = y + bias.astype(jnp.float32)
        return y.astype(self.dtype)
