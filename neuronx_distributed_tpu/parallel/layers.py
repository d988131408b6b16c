"""Tensor-parallel layers (GSPMD production path).

TPU-native re-design of the reference's Megatron TP modules
(``parallel_layers/layers.py``: ``ColumnParallelLinear`` :372-516,
``RowParallelLinear`` :519-660, ``ParallelEmbedding`` :97-205).  Instead of
hand-written autograd Functions with explicit all-gather / all-reduce /
reduce-scatter calls (``layers.py:208-334``), each module:

- creates its kernel with a :class:`flax.linen.Partitioned` metadata spec
  (column-parallel → sharded on the output dim, row-parallel → input dim,
  embedding → vocab dim), and
- constrains its activations with ``with_sharding_constraint`` so GSPMD
  inserts exactly the Megatron collectives, including the backward-pass
  conjugates.  The overlap the reference implements by hand
  (``layers.py:270-305``) the compiler recovers only in part: on the 2x2 host
  94% of the collective time of the tp = 4 training step stood alone (ledger,
  PR 48: ``collective_exposed_share`` 7.39 of ``collective_time_share``
  7.86).  The scatters and the backward's gathers it does put under matmuls
  (windowed einsums, async collective fusions); the FORWARD gather before a
  sequence-parallel column projection it emits synchronous, because the
  gather's only consumer is the matmul.  ``parallel/collective_matmul.py``
  gives it something to put beside the gather — the projection cut in pieces
  along the batch, piece ``i + 1``'s gather under piece ``i``'s matmul — and
  the q/k/v projection (``parallel/qkv.py``) is cut so where its local
  columns reach ``GATHER_MIN_WIDTH`` (the chip's peak over the measured gather
  speed, derived there).  The layers of this file are NOT cut: gate-up's
  products come out fused-axis-major, joining the pieces is a copy of 224 MiB,
  and the cut lost 0.2% of the tp4 step where q/k/v's won 1.8% (``PERF.md``
  §6, PR 49).

Sequence parallelism (Megatron-SP, reference ``mappings.py:198-250`` +
``layers.py:230-238,311-324``) is an activation-sharding choice here: SP
regions carry activations as ``[batch, seq/TP, hidden]``; entering a column-
parallel layer XLA all-gathers the sequence dim, and a row-parallel layer's
output constraint reduce-scatters back onto it.

Fused projections (reference ``stride=`` for QKV / gate-up,
``layers.py:372-516``, ``modeling_llama_nxd.py:142-150``) are expressed
shape-wise: ``n_fused > 1`` keeps a leading fused axis on the kernel so every
TP shard holds matching slices of each fused part — no interleaving tricks.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_tpu.obs import startup
from neuronx_distributed_tpu.parallel.mesh import (
    SEQUENCE_AXES,
    TENSOR_AXES,
    get_mesh,
    model_parallel_is_initialized,
)
from neuronx_distributed_tpu.utils.common import divide

Dtype = Any
Initializer = Callable[..., jax.Array]

_U = P.UNCONSTRAINED


@startup.phased("weights")
def init_sharded_params(module: nn.Module, rng: jax.Array, *example_inputs,
                        spec_map: Callable[[Any, Any], Any] | None = None):
    """``module.init`` with every parameter BORN sharded over the global
    mesh: shapes and partition specs come from an abstract evaluation, and
    the init program's ``out_shardings`` place each leaf as it is created —
    the whole unsharded model never sits on one device (at 7B widths that
    one device would be chip 0, and it would not fit).  ``example_inputs``
    are abstract-evaluated only.  ``spec_map(specs, abstract_params)`` may
    rewrite the specs (FSDP adds dp).  Returns ``(params, param_specs)``,
    params unboxed."""
    mesh = get_mesh()
    abs_params = jax.eval_shape(module.init, rng, *example_inputs)
    specs = nn.get_partition_spec(abs_params)
    if spec_map is not None:
        specs = spec_map(specs, nn.unbox(abs_params))
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    def init_sharded(r, *a):    # named: the start-up account lists programs
        return nn.unbox(module.init(r, *a))

    init = jax.jit(init_sharded, out_shardings=shardings)
    return init(rng, *example_inputs), specs


def shard_activation(x: jax.Array, spec: P) -> jax.Array:
    """Constrain ``x``'s sharding over the global mesh (no-op if no mesh).

    Inside a partial-manual ``shard_map`` region (the pipeline engine makes
    ``pp`` manual) the constraint must be expressed against the *abstract*
    context mesh — a NamedSharding over the concrete mesh carries all-Auto
    axis types and is rejected by jax's canonicalization when any axis is
    Manual in context."""
    if not model_parallel_is_initialized():
        return x
    abstract = jax.sharding.get_abstract_mesh()
    if abstract.axis_names:  # inside jit/shard_map: use the context mesh
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(abstract, spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(get_mesh(), spec))


def trailing_spec(ndim: int, **dims: Any) -> P:
    """Build a PartitionSpec that pins only dims addressed from the end.

    ``trailing_spec(3, last=TENSOR_AXES)`` → P(U, U, ('kvr','tp')).
    Keys: ``last`` (features dim), ``seq`` (dim -2).
    """
    entries = [_U] * ndim
    if "last" in dims:
        entries[-1] = dims["last"]
    if "seq" in dims and ndim >= 2:
        entries[-2] = dims["seq"]
    return P(*entries)


class ColumnParallelLinear(nn.Module):
    """Linear with output-dim sharding (reference ``layers.py:372-516``).

    Args:
      features: global output size (sum over TP shards).
      n_fused: number of fused sub-projections (QKV=3, gate-up=2).  When >1
        the kernel carries an explicit fused axis and the output is returned
        as ``[..., n_fused, features // n_fused]`` so each TP shard holds
        matching slices of every part (TPU-native form of reference
        ``stride=``).
      gather_output: all-gather the output so every shard sees the full
        feature dim (reference ``gather_output=True``).
      sequence_parallel: input activations are sequence-sharded
        ``[batch, seq/TP, hidden]``; XLA all-gathers seq before the matmul.
    """

    features: int
    use_bias: bool = True
    gather_output: bool = False
    sequence_parallel: bool = False
    n_fused: int = 1
    # LoRA (low-rank adaptation): rank > 0 adds a frozen-base-friendly
    # ``y += (alpha/r) * (x @ A) @ B`` path.  A ``[in, r]`` is replicated,
    # B follows the kernel's output sharding and starts at ZERO (the adapter
    # begins as the identity).  Freeze the base with
    # ``peft.lora_trainable`` + ``initialize_parallel_optimizer(trainable=)``.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    kernel_init: Initializer = nn.initializers.lecun_normal()
    bias_init: Initializer = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        in_features = x.shape[-1]
        per_fused = divide(self.features, self.n_fused)

        if self.n_fused == 1:
            kernel = self.param(
                "kernel",
                nn.with_partitioning(self.kernel_init, (None, TENSOR_AXES)),
                (in_features, self.features),
                self.param_dtype,
            )
        else:
            kernel = self.param(
                "kernel",
                nn.with_partitioning(self.kernel_init, (None, None, TENSOR_AXES)),
                (in_features, self.n_fused, per_fused),
                self.param_dtype,
            )

        x = x.astype(self.dtype)
        if self.sequence_parallel:
            x = shard_activation(x, trailing_spec(x.ndim, seq=SEQUENCE_AXES, last=None))
        kernel = jnp.asarray(kernel, self.dtype)

        if self.n_fused == 1:
            y = jax.lax.dot_general(
                x, kernel, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=self.dtype
            )
        else:
            y = jnp.einsum("...h,hfp->...fp", x, kernel, preferred_element_type=self.dtype)
        # The load-bearing constraint: output sharded on the feature dim makes
        # GSPMD insert the Megatron collectives (and their bwd conjugates).
        y = shard_activation(y, trailing_spec(y.ndim, last=TENSOR_AXES))

        if self.lora_rank > 0:
            r = self.lora_rank
            a = self.param(
                "lora_a",
                nn.with_partitioning(nn.initializers.lecun_normal(), (None, None)),
                (in_features, r), self.param_dtype,
            )
            xa = jnp.einsum("...h,hr->...r", x, jnp.asarray(a, self.dtype),
                            preferred_element_type=self.dtype)
            if self.n_fused == 1:
                b = self.param(
                    "lora_b",
                    nn.with_partitioning(nn.initializers.zeros_init(), (None, TENSOR_AXES)),
                    (r, self.features), self.param_dtype,
                )
                delta = jnp.einsum("...r,rp->...p", xa, jnp.asarray(b, self.dtype),
                                   preferred_element_type=self.dtype)
            else:
                b = self.param(
                    "lora_b",
                    nn.with_partitioning(nn.initializers.zeros_init(),
                                         (None, None, TENSOR_AXES)),
                    (r, self.n_fused, per_fused), self.param_dtype,
                )
                delta = jnp.einsum("...r,rfp->...fp", xa, jnp.asarray(b, self.dtype),
                                   preferred_element_type=self.dtype)
            y = y + (self.lora_alpha / r) * delta

        if self.use_bias:
            if self.n_fused == 1:
                bias = self.param(
                    "bias",
                    nn.with_partitioning(self.bias_init, (TENSOR_AXES,)),
                    (self.features,),
                    self.param_dtype,
                )
            else:
                bias = self.param(
                    "bias",
                    nn.with_partitioning(self.bias_init, (None, TENSOR_AXES)),
                    (self.n_fused, per_fused),
                    self.param_dtype,
                )
            y = y + jnp.asarray(bias, self.dtype)

        if self.gather_output:
            y = shard_activation(y, trailing_spec(y.ndim, last=None))
        return y


class RowParallelLinear(nn.Module):
    """Linear with input-dim sharding (reference ``layers.py:519-660``).

    The matmul contracts over the sharded input dim, so each shard produces a
    partial sum; the output constraint makes GSPMD finish it with an
    all-reduce (``input_is_parallel`` + dense output, reference
    ``layers.py:654-658``) or a reduce-scatter onto the sequence dim
    (``sequence_parallel``)."""

    features: int
    use_bias: bool = True
    input_is_parallel: bool = True
    sequence_parallel: bool = False
    # Sub-axis order of the sharded input dim.  Attention outputs arrive in
    # q-head order — sharded ('tp','kvr') — so the o_proj sets this to match
    # and no resharding happens between attention and projection.
    input_partition_axes: tuple = TENSOR_AXES
    # LoRA: A follows the kernel's input sharding (the x @ A contraction gets
    # the same psum as the base matmul), B is replicated and starts at zero.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    kernel_init: Initializer = nn.initializers.lecun_normal()
    bias_init: Initializer = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        in_features = x.shape[-1]
        kernel = self.param(
            "kernel",
            nn.with_partitioning(self.kernel_init, (self.input_partition_axes, None)),
            (in_features, self.features),
            self.param_dtype,
        )
        x = x.astype(self.dtype)
        if self.input_is_parallel:
            x = shard_activation(x, trailing_spec(x.ndim, last=self.input_partition_axes))
        y = jax.lax.dot_general(
            x,
            jnp.asarray(kernel, self.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=self.dtype,
        )
        if self.sequence_parallel:
            y = shard_activation(y, trailing_spec(y.ndim, seq=SEQUENCE_AXES, last=None))
        else:
            y = shard_activation(y, trailing_spec(y.ndim, last=None))
        if self.lora_rank > 0:
            r = self.lora_rank
            a = self.param(
                "lora_a",
                nn.with_partitioning(nn.initializers.lecun_normal(),
                                     (self.input_partition_axes, None)),
                (in_features, r), self.param_dtype,
            )
            xa = jnp.einsum("...h,hr->...r", x, jnp.asarray(a, self.dtype),
                            preferred_element_type=self.dtype)
            # the contraction runs over the sharded dim: replicating the
            # result makes GSPMD finish the partial sums (same psum as y's)
            xa = shard_activation(xa, trailing_spec(xa.ndim, last=None))
            b = self.param(
                "lora_b",
                nn.with_partitioning(nn.initializers.zeros_init(), (None, None)),
                (r, self.features), self.param_dtype,
            )
            delta = jnp.einsum("...r,rp->...p", xa, jnp.asarray(b, self.dtype),
                               preferred_element_type=self.dtype)
            y = y + (self.lora_alpha / r) * delta
        if self.use_bias:
            # Bias is replicated and added after the reduction (reference adds
            # bias post all-reduce on the full output, layers.py:650-659).
            bias = self.param("bias", self.bias_init, (self.features,), self.param_dtype)
            y = y + jnp.asarray(bias, self.dtype)
        return y


class ParallelEmbedding(nn.Module):
    """Vocab-sharded embedding (reference ``layers.py:97-205``).

    The table is sharded along the vocab dim; GSPMD lowers the sharded take
    to the same mask-local-lookup + psum the reference writes by hand
    (out-of-range mask + all-reduce combine, ``layers.py:182-205``)."""

    num_embeddings: int
    features: int
    sequence_parallel_output: bool = False
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    embedding_init: Initializer = nn.initializers.normal(stddev=0.02)

    def setup(self):
        # setup-style (not compact) so ``attend`` can reuse the table for
        # tied LM heads
        self.embedding = self.param(
            "embedding",
            nn.with_partitioning(self.embedding_init, (TENSOR_AXES, None)),
            (self.num_embeddings, self.features),
            self.param_dtype,
        )

    def __call__(self, ids: jax.Array) -> jax.Array:
        y = jnp.take(jnp.asarray(self.embedding, self.dtype), ids, axis=0)
        if self.sequence_parallel_output:
            # Model enters its first SP region right after the embedding
            # (reference scatter_to_sequence_parallel_region,
            # modeling_llama_nxd.py:530-532).
            y = shard_activation(y, trailing_spec(y.ndim, seq=SEQUENCE_AXES, last=None))
        else:
            y = shard_activation(y, trailing_spec(y.ndim, last=None))
        return y

    def attend(self, x: jax.Array) -> jax.Array:
        """Project hidden states onto the (tied) table: ``[..., H] →
        [..., V]`` with the vocab dim sharded — the tied-embedding LM head
        (the reference handles tying via shared-weight registration,
        ``pipeline/partition.py:225-250``; here it is literal param reuse)."""
        y = jnp.einsum(
            "...h,vh->...v", x.astype(self.dtype), jnp.asarray(self.embedding, self.dtype),
            preferred_element_type=self.dtype,
        )
        return shard_activation(y, trailing_spec(y.ndim, last=TENSOR_AXES))
