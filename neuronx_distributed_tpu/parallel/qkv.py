"""GQA QKV projection with KV-head replication across a TP sub-axis.

TPU-native re-design of the reference's ``GQAQKVColumnParallelLinear``
(``modules/qkv_linear.py``).  The reference solves "num KV heads < TP degree"
by physically repeating the KV weight ``kv_size_multiplier`` times before
sharding and summing KV grads over a dedicated KV-shared process group of
stride ``tp/kv_size_multiplier`` (``qkv_linear.py:26-62,78-118,208-222``).

Here no weight is ever repeated.  The mesh factors the full TP degree into
``kvr × tp`` (``parallel/mesh.py``), and:

- **Q** kernels shard their head dim over ``('tp', 'kvr')`` — tp-major, so
  device ``(kvr=o, tp=i)`` holds the q-head block ``i*kvr_size + o``;
- **K/V** kernels shard their head dim over ``'tp'`` only, replicated along
  ``kvr``.

With ``groups = num_heads // num_kv_heads`` q-heads per kv-head, device
``(o, i)`` holds q heads ``[i*g + o*g/kvr, ...)`` — exactly the q heads whose
kv head is head ``i``, the same pairing the reference builds with strided
KV groups.  Attention then needs zero cross-device communication, and the
reference's KV-grad correction (psum over the KV group + divide by the
multiplier) is what GSPMD derives automatically for a kvr-replicated kernel.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.parallel import collective_matmul
from neuronx_distributed_tpu.parallel.layers import shard_activation, trailing_spec
from neuronx_distributed_tpu.parallel.mesh import (
    KV_REPLICA_AXIS,
    SEQUENCE_AXES,
    TENSOR_AXIS,
    get_kv_size_multiplier,
    get_tensor_parallel_size,
    model_parallel_is_initialized,
)

# Head-dim sharding axes for Q (tp-major: kv-group-major ordering) and KV.
Q_HEAD_AXES = (TENSOR_AXIS, KV_REPLICA_AXIS)
KV_HEAD_AXES = TENSOR_AXIS

Dtype = Any
Initializer = Callable[..., jax.Array]


def validate_gqa_sharding(num_heads: int, num_kv_heads: int) -> None:
    """Check head counts against the live mesh, guiding kv_size_multiplier
    choice (the reference validates in ``qkv_linear.py:363-380``)."""
    if not model_parallel_is_initialized():
        return
    tp_full = get_tensor_parallel_size()
    kvr = get_kv_size_multiplier()
    tp_inner = tp_full // kvr
    if num_heads % tp_full != 0:
        raise ValueError(f"num_heads={num_heads} not divisible by TP degree {tp_full}")
    if num_kv_heads % tp_inner != 0:
        raise ValueError(
            f"num_kv_heads={num_kv_heads} not divisible by tp={tp_inner} (= TP degree "
            f"{tp_full} / kv_size_multiplier {kvr}); initialize the mesh with "
            f"kv_size_multiplier={tp_full // num_kv_heads if num_kv_heads and tp_full % num_kv_heads == 0 else '<tp/num_kv_heads>'}"
        )


class GQAQKVColumnParallelLinear(nn.Module):
    """Computes Q, K, V projections with GQA-aware sharding.

    Returns ``(q, k, v)`` shaped ``[..., num_heads, head_dim]`` /
    ``[..., num_kv_heads, head_dim]`` (reference fwd computes the three
    separately too, ``qkv_linear.py:181-185``)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    use_bias: bool = False
    sequence_parallel: bool = False
    # LoRA on the q/k/v projections: per-projection A ``[in, r]`` replicated,
    # B shaped/sharded like the projection's head layout, zero-initialized.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = ("q", "v")  # the standard LoRA targets
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    kernel_init: Initializer = nn.initializers.lecun_normal()
    bias_init: Initializer = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        if self.num_heads % self.num_kv_heads != 0:
            raise ValueError(
                f"num_heads={self.num_heads} not divisible by num_kv_heads={self.num_kv_heads}"
            )
        validate_gqa_sharding(self.num_heads, self.num_kv_heads)
        in_features = x.shape[-1]

        wq = self.param(
            "q_kernel",
            nn.with_partitioning(self.kernel_init, (None, Q_HEAD_AXES, None)),
            (in_features, self.num_heads, self.head_dim),
            self.param_dtype,
        )
        wk = self.param(
            "k_kernel",
            nn.with_partitioning(self.kernel_init, (None, KV_HEAD_AXES, None)),
            (in_features, self.num_kv_heads, self.head_dim),
            self.param_dtype,
        )
        wv = self.param(
            "v_kernel",
            nn.with_partitioning(self.kernel_init, (None, KV_HEAD_AXES, None)),
            (in_features, self.num_kv_heads, self.head_dim),
            self.param_dtype,
        )

        x = x.astype(self.dtype)
        if self.sequence_parallel:
            x = shard_activation(x, trailing_spec(x.ndim, seq=SEQUENCE_AXES))

        dtype = self.dtype

        def base(x, w, head_axes):
            y = jnp.einsum("...h,hnd->...nd", x, jnp.asarray(w, dtype),
                           preferred_element_type=dtype)
            # head dim sits at -2 ([..., n_heads, head_dim])
            return shard_activation(y, trailing_spec(y.ndim, seq=head_axes))

        def proj(w, head_axes, name):
            y = base(x, w, head_axes)
            if self.lora_rank > 0 and name in self.lora_targets:
                r = self.lora_rank
                n_heads = w.shape[1]
                a = self.param(
                    f"lora_a_{name}",
                    nn.with_partitioning(nn.initializers.lecun_normal(), (None, None)),
                    (in_features, r), self.param_dtype,
                )
                b = self.param(
                    f"lora_b_{name}",
                    nn.with_partitioning(nn.initializers.zeros_init(),
                                         (None, head_axes, None)),
                    (r, n_heads, self.head_dim), self.param_dtype,
                )
                xa = jnp.einsum("...h,hr->...r", x, jnp.asarray(a, self.dtype),
                                preferred_element_type=self.dtype)
                delta = jnp.einsum("...r,rnd->...nd", xa, jnp.asarray(b, self.dtype),
                                   preferred_element_type=self.dtype)
                y = y + (self.lora_alpha / r) * delta
            return y

        pieces = 1
        if self.sequence_parallel and self.lora_rank == 0:
            pieces = collective_matmul.gather_pieces(
                x, (self.num_heads + 2 * self.num_kv_heads) * self.head_dim)
        if pieces == 1:
            q = proj(wq, Q_HEAD_AXES, "q")
            k = proj(wk, KV_HEAD_AXES, "k")
            v = proj(wv, KV_HEAD_AXES, "v")
        else:
            # a piece's gather rides under its neighbour's three matmuls
            q, k, v = collective_matmul.in_pieces(
                lambda x, wq, wk, wv: (base(x, wq, Q_HEAD_AXES),
                                       base(x, wk, KV_HEAD_AXES),
                                       base(x, wv, KV_HEAD_AXES)),
                pieces, x, wq, wk, wv)

        if self.use_bias:
            bq = self.param(
                "q_bias",
                nn.with_partitioning(self.bias_init, (Q_HEAD_AXES, None)),
                (self.num_heads, self.head_dim),
                self.param_dtype,
            )
            bk = self.param(
                "k_bias",
                nn.with_partitioning(self.bias_init, (KV_HEAD_AXES, None)),
                (self.num_kv_heads, self.head_dim),
                self.param_dtype,
            )
            bv = self.param(
                "v_bias",
                nn.with_partitioning(self.bias_init, (KV_HEAD_AXES, None)),
                (self.num_kv_heads, self.head_dim),
                self.param_dtype,
            )
            q = q + jnp.asarray(bq, self.dtype)
            k = k + jnp.asarray(bk, self.dtype)
            v = v + jnp.asarray(bv, self.dtype)
        return q, k, v
