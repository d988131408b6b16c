"""Paged KV-cache subsystem (ISSUE 5 tentpole).

Block-granular KV allocation with prefix reuse for the serving engine —
PagedAttention's memory model (Kwon et al., SOSP '23) and RadixAttention's
prefix sharing (Zheng et al., 2024) mapped onto static-shape JAX/pjit:

- :mod:`.allocator` — :class:`BlockAllocator`: host-side free-list page
  accounting with refcounted sharing, atomic allocation
  (:class:`PoolExhausted` takes nothing), copy-on-write, and no-leak /
  no-double-free invariant checks;
- :mod:`.prefix` — :class:`PrefixIndex`: a page-granular token trie mapping
  padded prompt prefixes to shared page chains (full-prompt hits carry the
  prefill logits, so repeated prompts skip prefill compute), with LRU
  eviction of refcount-0 chains;
- :mod:`.pool` — :class:`PagePool`: the preallocated
  ``[num_pages, kv_heads, page_size, head_dim]`` device arrays per layer
  (kv over tp, page axis a global unsharded pool) plus sizing arithmetic;
- :mod:`.transfer` — :func:`export_chain` / :func:`import_chain`: move a
  committed page chain between pools (fp and int8 layouts) with
  transactional failure semantics — the disaggregated fleet's KV
  migration and fleet-global prefix-cache primitive.

The serving integration lives one layer up:
``serving.paged.PagedKVManager`` glues these onto the engine's slot table,
``trace.ParallelInferenceModel`` compiles the paged phase programs
(``decode_pages`` / ``prefill_chunk_pages`` / ``copy_page``), and
``models.llama`` carries the block-table scatter — the pool's one writer
of K/V rows — and the gather decode path.
"""

from neuronx_distributed_tpu.kvcache.allocator import (
    NULL_PAGE,
    BlockAllocator,
    PoolExhausted,
)
from neuronx_distributed_tpu.kvcache.pool import (
    GATHER_BYTES_TOTAL,
    PageKinds,
    PagePool,
    init_page_pool_caches,
    page_kinds,
)
from neuronx_distributed_tpu.kvcache.prefix import (
    PAD,
    PrefixIndex,
    is_padding_key,
    page_keys,
)
from neuronx_distributed_tpu.kvcache.transfer import (
    PAGES_EXPORTED_TOTAL,
    PAGES_IMPORTED_TOTAL,
    ChainExport,
    TransferError,
    export_chain,
    import_chain,
)

__all__ = [
    "BlockAllocator",
    "ChainExport",
    "GATHER_BYTES_TOTAL",
    "NULL_PAGE",
    "PAD",
    "PAGES_EXPORTED_TOTAL",
    "PAGES_IMPORTED_TOTAL",
    "PageKinds",
    "PagePool",
    "page_kinds",
    "PoolExhausted",
    "PrefixIndex",
    "TransferError",
    "export_chain",
    "import_chain",
    "init_page_pool_caches",
    "is_padding_key",
    "page_keys",
]
