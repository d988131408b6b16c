"""Device-side page pool for the paged KV cache (PagedAttention, Kwon et
al. SOSP '23, mapped onto static-shape pjit).

The contiguous serving cache reserves ``[B, max_total_len]`` KV per slot —
HBM scales with the *worst case* of every slot at once, and that, not
compute, caps concurrency.  The page pool breaks the coupling: one
preallocated HEAD-MAJOR ``[num_pages, kv_heads, page_size, head_dim]`` pair
per layer (one page of one kv head is a whole ``(page, head_dim)`` trailing
slab — the block the paged-attention kernel DMAs, ``ops.paged_attention``),
and requests hold integer *block tables* mapping their logical cache pages
to physical pages.  Left-padding pages and unwritten decode tail pages
back onto the shared NULL page (index 0, content never written), and prompt
pages shared through the :class:`~.prefix.PrefixIndex` exist once.

Shapes are static — the pool is one allocation for the process lifetime,
pjit-compatible by construction: the decode program gathers ``pool[block
table]`` (the same ``[B, T]`` view the contiguous path attends over, so the
band-mask attention core is unchanged), and page writes are
``dynamic_update_slice`` at traced page ids.  Sharding matches the
contiguous caches: kv-heads over ``tp`` when divisible; the page axis is a
GLOBAL pool and stays unsharded over ``dp`` (block tables address arbitrary
pages — a dp-sharded page axis would turn every gather into a collective).
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from neuronx_distributed_tpu.parallel.mesh import (
    TENSOR_AXIS,
    get_mesh,
    model_parallel_is_initialized,
    named_sharding,
)
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# registry counter: bytes the paged GATHER decode path spends
# rematerializing per-slot [B, T] contiguous K/V clones from the pool —
# what the block-table-native kernel (ops.paged_attention) saves.  Stays
# ZERO on the kernel path (the int8 acceptance gate: quantized serving
# with the kernel never materializes a dequantized history).
GATHER_BYTES_TOTAL = "kvcache/gather_bytes_total"


def init_page_pool_caches(
    num_layers: int,
    num_pages: int,
    page_size: int,
    num_kv_heads: int,
    head_dim: int,
    dtype: Any = jnp.bfloat16,
    quant: Optional[str] = None,
) -> List[Tuple[jax.Array, ...]]:
    """Zero page-pool caches ``[NP, NKV, page, D]`` per layer, kv-heads
    sharded over tp when divisible (the same policy as the contiguous
    ``init_kv_caches``); the page axis is unsharded — it is a global pool.

    ``quant="int8"`` switches each layer's entry from the fp pair
    ``(k, v)`` to the six-tuple ``(k int8, v int8, k_scale, k_zero,
    v_scale, v_zero)`` with one fp32 scale/zero per physical page (see
    :mod:`.quant`) — the structural marker the model's block-table
    scatter/gather keys its dequantize-in-the-gather path on."""
    shape = (num_pages, num_kv_heads, page_size, head_dim)
    if quant not in (None, "int8"):
        raise ValueError(f"unknown KV quantization {quant!r} "
                         "(supported: 'int8')")
    # every leaf is BORN with its sharding (never staged whole on the
    # default device: a pool sized for a tp mesh does not fit one chip)
    page_sh = scale_sh = None
    if model_parallel_is_initialized():
        mesh = get_mesh()
        kv_axes = (TENSOR_AXIS
                   if num_kv_heads % mesh.shape[TENSOR_AXIS] == 0 else None)
        if kv_axes is None and mesh.shape[TENSOR_AXIS] > 1:
            logger.warning(
                "page pool kv head dim (%d) not divisible by tp (%d); "
                "replicating", num_kv_heads, mesh.shape[TENSOR_AXIS])
        page_sh = named_sharding(None, kv_axes, None, None)
        scale_sh = named_sharding(None)  # per-page params: replicated

    def pages(dt):
        return jnp.zeros(shape, dt, device=page_sh)

    def params():
        return jnp.zeros((num_pages,), jnp.float32, device=scale_sh)

    if quant is None:
        return [(pages(dtype), pages(dtype)) for _ in range(num_layers)]
    return [(pages(jnp.int8), pages(jnp.int8),
             params(), params(), params(), params())
            for _ in range(num_layers)]


class PagePool:
    """The preallocated device pool plus its sizing arithmetic.

    ``caches`` is the live pytree the engine threads through the compiled
    paged phase fns (donated every decode step — treat the attribute as the
    initial value, not a persistent view).  The class is deliberately thin:
    page *accounting* lives in the host-side
    :class:`~.allocator.BlockAllocator`, device *programs* on the serving
    wrapper (``decode_pages`` / ``prefill_chunk_pages`` / ``copy_page``)."""

    def __init__(
        self,
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
        quant: Optional[str] = None,
    ):
        if num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is the NULL page), "
                f"got {num_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.dtype = dtype
        self.quant = quant
        self.caches = init_page_pool_caches(
            num_layers, num_pages, page_size, num_kv_heads, head_dim, dtype,
            quant=quant)

    @property
    def page_bytes(self) -> int:
        """HBM bytes one page costs across all layers (k + v, plus the
        per-page scale/zero params under int8 quantization — honest
        accounting: the quantized pool pays for its metadata)."""
        from neuronx_distributed_tpu.kvcache.quant import page_layer_bytes

        return self.num_layers * page_layer_bytes(
            self.page_size, self.num_kv_heads, self.head_dim, self.quant,
            self.dtype)

    @property
    def total_bytes(self) -> int:
        return self.num_pages * self.page_bytes

    @staticmethod
    def pages_for_budget(budget_bytes: int, num_layers: int, page_size: int,
                         num_kv_heads: int, head_dim: int,
                         dtype: Any = jnp.bfloat16,
                         quant: Optional[str] = None) -> int:
        """How many pool pages a given HBM budget buys — the sizing half of
        the paged-vs-contiguous comparison (a contiguous ``[B, T]`` cache's
        budget is ``B * T / page_size`` pages).  ``quant="int8"`` roughly
        doubles the answer at a fixed budget versus bf16 (1 byte/element +
        four fp32 page params instead of 2 bytes/element)."""
        from neuronx_distributed_tpu.kvcache.quant import page_layer_bytes

        per_page = num_layers * page_layer_bytes(
            page_size, num_kv_heads, head_dim, quant, dtype)
        return max(int(budget_bytes // per_page), 0)
