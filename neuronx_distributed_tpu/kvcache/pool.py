"""Device-side page pool for the paged KV cache (PagedAttention, Kwon et
al. SOSP '23, mapped onto static-shape pjit).

The contiguous serving cache reserves ``[B, max_total_len]`` KV per slot —
HBM scales with the *worst case* of every slot at once, and that, not
compute, caps concurrency.  The page pool breaks the coupling: one
preallocated HEAD-MAJOR ``[num_pages, kv_heads, page_size, head_dim]`` pair
per layer (one page of one kv head is a whole ``(page, head_dim)`` trailing
slab — the block the paged-attention kernel DMAs, ``ops.paged_attention``),
and requests hold integer *block tables* mapping their logical cache pages
to physical pages.  Heads of HALF a lane row (``head_dim`` 64) are kept two
to a row, ``[num_pages, kv_heads / 2, page_size, 128]`` (:func:`page_layout`):
the device lays a minor dimension out on 128 lanes, so a 64-wide row would
take twice its bytes there.  Left-padding pages and unwritten decode tail pages
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

import dataclasses
import math
import numbers
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


# lanes of the device's tiles: an array's minor dimension is laid out on
# whole lane rows, its second minor on whole sublane tiles of its dtype
LANES = 128
_SUBLANES = 8


def page_layout(num_kv_heads: int, head_dim: int) -> Tuple[int, int]:
    """``(heads, width)`` of a K/V page as the pool keeps it, from the
    shapes alone.  A head of half a lane row shares its row with its
    neighbour — kv heads ``2j`` and ``2j + 1`` are lanes ``[0, D)`` and
    ``[D, 2D)`` of pool head ``j``, which is the plain reshape of ``[...,
    NKV, D]`` to ``[..., NKV / 2, 2D]`` — so that a page costs the bytes of
    what it holds.  Every other shape, an odd head count included, is kept
    as it is (a 64-wide head alone in its row is laid out on 128 lanes)."""
    if 2 * head_dim == LANES and num_kv_heads % 2 == 0:
        return num_kv_heads // 2, LANES
    return num_kv_heads, head_dim


def laid_out_bytes(shape, dtype) -> int:
    """Bytes an array of ``shape`` takes on the device, whose tiles pad the
    minor dimension to :data:`LANES` and the second minor to the sublanes
    of ``dtype`` (8 of 4 bytes, 16 of 2, 32 of 1)."""
    item = jnp.dtype(dtype).itemsize
    tile = _SUBLANES * max(4 // item, 1)
    *lead, rows, cols = shape
    return (math.prod(lead) * -(-rows // tile) * tile
            * -(-cols // LANES) * LANES * item)


# what a layer keeps for a live sequence: K/V pages; K/V pages it chooses
# among, with compressed keys beside them; a row of each of its state
# arrays; pages of ONE latent row a token (no K/V pair, no kv-head axis:
# ``[NP, page, latent_dim]``, ``ops.latent_attention``); nothing (a layer
# without a mixer)
CACHE_KINDS = ("pages", "selected_pages", "state", "latent", "none")
# ... those of them whose entries are pages the allocator counts
PAGED_KINDS = ("pages", "selected_pages", "latent")
# what each kind's entries are carried through beside plain serving: a verify
# round's rewind, int8 rows, LoRA deltas, a head axis to shard, a chain
# another prompt may share (a selecting layer writes a row's pages at a shift
# of its own, a state row is no chain; a latent page holds the same rows
# whoever wrote it), export and import of chains
_ALL = ("spec_k", "kv_quant", "adapter_store", "tp", "prefix_cache",
        "migration")
CARRIES = {"pages": _ALL, "selected_pages": (), "state": (),
           "latent": ("prefix_cache",), "none": _ALL}
# ... how a refusal names each, and why a layer of another kind, or pages of
# several kinds (None: never their matter), do not carry it
_ASKS = (
    ("spec_k", "speculative decoding (spec_k)", ": no state roll-back",
     ": a rejected tail rewinds rows whose band was given back"),
    ("kv_quant", "an int8 page pool (kv_quant)", "",
     ": one array layout a layer, one page-id space"),
    ("adapter_store", "LoRA adapter pages (adapter_store)", "", ""),
    ("tp", "tensor parallelism (tp > 1)", "", None),
    ("prefix_cache", "the prefix index (prefix_cache=True)",
     ": a model that keeps no page has no chain to share",
     ": a chain with holes is no prefix, and the index holds pages of one "
     "kind"))
_NO_CHAINS = ("KV migration moves page chains: a model with recurrent state "
              "rows or block-sparse page layouts has none to move")
_NOT_MOVED = {
    "selected_pages": _NO_CHAINS, "state": _NO_CHAINS,
    "latent": "KV migration moves K/V page chains: chains of latent pages "
              "are not carried through export and import yet"}


@dataclasses.dataclass(frozen=True)
class PageKinds:
    """The KINDS of K/V page a model's layers keep: layers with the same
    causal window (or none) are one kind — one page-id space, one block
    table a slot, one page count of the pool — because their pages live and
    die together: a layer that attends everything keeps a sequence's whole
    history, a layer with a window only the keys a row can still be asked
    for.  ``windows[k]`` is kind ``k``'s window (None: everything), kinds in
    the order their first layer appears; ``of_layer[i]`` layer ``i``'s kind.
    A model without windows, or with one window for every layer, has ONE
    kind."""

    windows: Tuple[Optional[int], ...] = (None,)
    of_layer: Tuple[int, ...] = ()

    def __len__(self) -> int:
        return len(self.windows)

    def layers(self, kind: int) -> int:
        """How many layers keep pages of ``kind``."""
        return sum(k == kind for k in self.of_layer)

    def weights(self) -> Tuple[int, ...]:
        """Each kind's bytes a page in the smallest whole units: its layer
        count over the counts' greatest common divisor (1 for one kind) —
        what a page count of several kinds is summed by."""
        counts = [max(self.layers(k), 1) for k in range(len(self))]
        g = math.gcd(*counts)
        return tuple(c // g for c in counts)

    def window_pages(self, max_total_len: int, chunk_tokens: int,
                     page_size: int) -> Tuple[Optional[int], ...]:
        """The most pages of each kind a slot can hold at once where the
        band gives pages back: the window, the widest prefill chunk and a
        page of misalignment.  None: the kind keeps a row's whole history
        (no window, or one no row of ``max_total_len`` outgrows) and never
        frees before release."""
        return tuple(
            None if w is None or w >= max_total_len
            else math.ceil((w + chunk_tokens) / page_size) + 1
            for w in self.windows)


def page_kinds(cfg) -> PageKinds:
    """The page kinds of a model config, from its layers' windows
    (``layer_windows`` and ``page_kind_of_layer``:
    ``models.llama.LlamaConfig``); a config that does not say has one kind
    that keeps everything."""
    by_layer = getattr(cfg, "layer_windows", None)
    if not by_layer:
        return PageKinds()
    return PageKinds(tuple(dict.fromkeys(by_layer)),
                     tuple(cfg.page_kind_of_layer))


@dataclasses.dataclass(frozen=True)
class CachePlan:
    """What :func:`cache_plan` resolved: some layer keeps a state row
    (``recurrent``) and none a page (``pageless``), the page kinds, whether
    window kinds give pages back (``free_behind``), whether the prefix index
    is on, and why the layers' chains are not migrated (None: they are)."""

    recurrent: bool
    pageless: bool
    page_kinds: PageKinds
    free_behind: bool
    prefix_cache: bool
    not_moved: Optional[str]

    def refuses_migration(self, frees: bool) -> Optional[str]:
        """Why no chain is exported or imported, None where they are:
        ``frees`` says that the manager gives pages back."""
        if self.not_moved is None and (frees or len(self.page_kinds) > 1):
            return ("KV migration moves whole page chains of ONE kind: a "
                    "model whose window layers give pages back, or whose "
                    "pages come in several kinds, has none to move (a model "
                    "of one kind keeps whole chains under prefix_cache=True)")
        return self.not_moved


def cache_plan(cfg, *, spec_k: int, kv_quant: Optional[str], adapters: bool,
               prefix_cache: Optional[bool], tp: int,
               max_total_len: int) -> CachePlan:
    """What a model's caches may be combined with: from its config (None, or
    no layer list: every layer keeps pages) and what the caller asked for,
    the :class:`CachePlan` — or the ``ValueError`` that names what is not
    carried (:data:`CARRIES`) rather than run wrong.  Where a layer's pages
    cannot be shared the prefix index is OFF, whatever was passed; a model of
    recurrent layers ALONE keeps no page and is admitted, finished and freed
    by its state row.

    Pages by layer KIND (:func:`page_kinds`): a kind whose window a row can
    outgrow gives its pages back as the band moves on (serving/paged.py) —
    unless something needs WHOLE chains: a verify round rewinds rows whose
    band would have been returned; an int8 page requantizes the whole page
    its neighbours were freed around; LoRA pages, the KV hand-off between
    replicas and a preempted request's resume pin move chains the prefix
    index vouches for.  A model of ONE kind asked for any of them keeps every
    page (the window only masks); left to the default (``prefix_cache``
    None) its pages come back and the index is off.  A model of SEVERAL
    kinds has no pool to fall back on — with a mask alone its pages are what
    the chip cannot hold, and the index is one kind's — so there they
    raise."""
    kept = set(getattr(cfg, "layer_caches", None) or ("pages",))

    def lacking(what):
        return any(what not in CARRIES[k] for k in kept)

    recurrent = "state" in kept
    pageless = recurrent and not kept & set(PAGED_KINDS)
    asked = {"spec_k": spec_k, "kv_quant": kv_quant is not None,
             "adapter_store": adapters, "tp": tp > 1,
             # an index asked of a hybrid of state rows and pages is off,
             # unsaid; asked of a model that keeps no page it is refused
             "prefix_cache": prefix_cache and pageless}
    refused = [what + why for name, what, why, _ in _ASKS
               if asked[name] and lacking(name)]
    if refused:
        from neuronx_distributed_tpu.models.hybrid import RECURRENT_NAMES

        raise ValueError(
            f"not carried through recurrent ({RECURRENT_NAMES}), "
            "page-selecting or latent layers yet: " + "; ".join(refused))
    if lacking("prefix_cache"):
        prefix_cache = False
    asked["prefix_cache"] = prefix_cache
    kinds = page_kinds(cfg)
    several = len(kinds) > 1
    whole_chains = [what + why for name, what, _, why in _ASKS
                    if asked[name] and why is not None]
    if several and whole_chains:
        raise ValueError(
            "not carried through pages of several kinds (layers of "
            "different windows), whose window layers give pages back, "
            "yet: " + "; ".join(whole_chains))
    free_behind = several or not whole_chains
    if prefix_cache is None:
        outgrown = any(w is not None and w < max_total_len
                       for w in kinds.windows)
        prefix_cache = not several and not (free_behind and outgrown)
        if outgrown and not prefix_cache:
            logger.info(
                "serving: window pages come back as the band moves on, "
                "so the prefix index is off%s", "" if several else
                " (prefix_cache=True keeps whole chains and the index; "
                "the window then only masks)")
    return CachePlan(
        recurrent, pageless, kinds, free_behind, bool(prefix_cache),
        next((_NOT_MOVED[k] for k in CACHE_KINDS
              if k in kept and "migration" not in CARRIES[k]), None))


def pages_of_kinds(num_pages, kinds: Optional[PageKinds]) -> Tuple[int, ...]:
    """``num_pages`` as one count a kind: an int is every kind's."""
    n = len(kinds) if kinds is not None else 1
    counts = ((int(num_pages),) * n if isinstance(num_pages, numbers.Integral)
              else tuple(int(p) for p in num_pages))
    if len(counts) != n:
        raise ValueError(
            f"num_pages {num_pages!r} names {len(counts)} page counts for a "
            f"model of {n} page kind(s)")
    return counts


@dataclasses.dataclass(frozen=True)
class LayerStates:
    """What each layer of a model with a layer LIST keeps for a sequence
    (``kinds``, one of :data:`CACHE_KINDS` a layer): K/V pages for the
    softmax layers only — with ``comp_slots`` compressed keys a page beside
    them where the layer selects pages — and, for the recurrent layers, one
    row a live sequence of each of the layer's state arrays
    (``state_arrays``: ``(shape, dtype name)`` each, ``[state_rows, *shape]``
    on the device — a lightning layer's one float32 state, a Mamba-2
    layer's scan state and convolution taps), which is neither paged nor
    shareable by page.  A ``"latent"`` layer's entry is ``(latents [NP,
    page, latent_dim],)``: pages as any other to the allocator, the block
    tables and the prefix index, ``latent_dim`` columns a token in place of
    a K/V pair a kv head.  A ``"none"`` layer's entry of the pool is
    ``()``.  A model whose every layer is recurrent keeps NO page
    (:attr:`paged` is 0): its pool is its state rows alone, a page costs
    nothing, and the serving engine admits by state rows
    (``serving.paged.PagedKVManager(pageless=True)``)."""

    kinds: Tuple[str, ...]
    comp_slots: int = 0
    state_rows: int = 0
    state_arrays: Tuple[Tuple[Tuple[int, ...], str], ...] = ()
    latent_dim: int = 0

    @staticmethod
    def for_config(cfg, page_size: int, state_rows: int
                   ) -> "Optional[LayerStates]":
        """From a model config that says what its layers keep
        (``layer_caches``, ``state_arrays``, ``selection_spec``:
        ``models.llama.LlamaConfig``); None where every layer keeps pages."""
        kinds = getattr(cfg, "layer_caches", None)
        if kinds is None:
            return None
        spec = cfg.selection_spec
        if spec is not None and page_size != spec.block_size:
            raise ValueError(
                f"selecting layers choose pages: page_size ({page_size}) "
                f"must equal the selection's block_size ({spec.block_size})")
        recurrent = "state" in kinds
        return LayerStates(
            kinds=tuple(kinds),
            comp_slots=(page_size // spec.kernel_stride if spec is not None
                        else 0),
            state_rows=state_rows if recurrent else 0,
            state_arrays=tuple(cfg.state_arrays) if recurrent else (),
            latent_dim=getattr(cfg, "latent_row_dim", 0))

    @property
    def recurrent(self) -> int:
        return sum(k == "state" for k in self.kinds)

    @property
    def paged(self) -> int:
        return sum(k in PAGED_KINDS for k in self.kinds)

    @property
    def state_shape(self) -> Tuple[int, ...]:
        """The first state array's shape: the recurrent state itself."""
        return self.state_arrays[0][0] if self.state_arrays else ()

    @property
    def state_row_bytes(self) -> int:
        """Bytes one state row costs across the recurrent layers."""
        return self.recurrent * sum(
            math.prod(shape) * jnp.dtype(dt).itemsize
            for shape, dt in self.state_arrays)


def init_page_pool_caches(
    num_layers: int,
    num_pages: int,
    page_size: int,
    num_kv_heads: int,
    head_dim: int,
    dtype: Any = jnp.bfloat16,
    quant: Optional[str] = None,
    layers: Optional[LayerStates] = None,
    kinds: Optional[PageKinds] = None,
) -> List[Tuple[jax.Array, ...]]:
    """Zero page-pool caches ``[NP, NKV, page, D]`` per layer (``[NP, NKV /
    2, page, 2D]`` where :func:`page_layout` pairs the heads), kv-heads
    sharded over tp when divisible (the same policy as the contiguous
    ``init_kv_caches``; paired heads by whole pairs); the page axis is
    unsharded — it is a global pool.
    With ``kinds`` of more than one kind, ``num_pages`` is a count a kind
    and a layer's arrays have ITS kind's.

    ``quant="int8"`` switches each layer's entry from the fp pair
    ``(k, v)`` to the six-tuple ``(k int8, v int8, k_scale, k_zero,
    v_scale, v_zero)`` with one fp32 scale/zero per physical page (see
    :mod:`.quant`) — the structural marker the model's block-table
    scatter/gather keys its dequantize-in-the-gather path on."""
    counts = pages_of_kinds(num_pages, kinds)
    if len(counts) > 1 and (quant is not None or layers is not None):
        raise ValueError("pages of several kinds (layers of different "
                         "windows) are carried through neither an int8 "
                         "pool nor a layer list")
    num_pages = counts[0]
    if quant not in (None, "int8"):
        raise ValueError(f"unknown KV quantization {quant!r} "
                         "(supported: 'int8')")
    # every leaf is BORN with its sharding (never staged whole on the
    # default device: a pool sized for a tp mesh does not fit one chip)
    page_sh = scale_sh = None
    pool_heads, pool_width = page_layout(num_kv_heads, head_dim)
    if model_parallel_is_initialized():
        mesh = get_mesh()
        kv_axes = (TENSOR_AXIS
                   if pool_heads % mesh.shape[TENSOR_AXIS] == 0 else None)
        if kv_axes is None and mesh.shape[TENSOR_AXIS] > 1:
            logger.warning(
                "page pool kv head dim (%d, %d to a lane row) not divisible "
                "by tp (%d); replicating", num_kv_heads,
                num_kv_heads // pool_heads, mesh.shape[TENSOR_AXIS])
        page_sh = named_sharding(None, kv_axes, None, None)
        scale_sh = named_sharding(None)  # per-page params: replicated

    def pages(dt, n=num_pages, paired=True):
        shape = ((pool_heads, page_size, pool_width) if paired
                 else (num_kv_heads, page_size, head_dim))
        return jnp.zeros((n,) + shape, dt, device=page_sh)

    def params():
        return jnp.zeros((num_pages,), jnp.float32, device=scale_sh)

    if layers is not None:
        # a layer list: an entry a layer, shaped by what it keeps — ``(k,
        # v)``, ``(k, v, compressed keys [NP, slots, NKV, D])``, a ``[R,
        # ...]`` array for each of ``state_arrays``, or ``()``
        if quant is not None:
            raise ValueError("an int8 pool is not carried through a layer "
                             "list (selected pages, state rows)")

        def entry(kind):
            if kind == "none":
                return ()
            if kind == "latent":
                return (jnp.zeros((num_pages, page_size, layers.latent_dim),
                                  dtype, device=scale_sh),)
            if kind == "state":
                return tuple(
                    jnp.zeros((layers.state_rows,) + shape, jnp.dtype(dt),
                              device=scale_sh)
                    for shape, dt in layers.state_arrays)
            if kind == "selected_pages":
                comp = jnp.zeros((num_pages, layers.comp_slots, num_kv_heads,
                                  head_dim), dtype, device=scale_sh)
                # a selecting layer reads its pages head by head beside
                # the compressed keys: kept as they are
                return (pages(dtype, paired=False),
                        pages(dtype, paired=False), comp)
            return (pages(dtype), pages(dtype))

        return [entry(k) for k in layers.kinds]
    if quant is None:
        of_layer = (kinds.of_layer if len(counts) > 1
                    else (0,) * num_layers)
        return [(pages(dtype, counts[k]), pages(dtype, counts[k]))
                for k in of_layer]
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
        layers: Optional[LayerStates] = None,
        kinds: Optional[PageKinds] = None,
    ):
        self.kinds = kinds if kinds is not None else PageKinds()
        # one count a kind; ``num_pages`` stays the first kind's (the only
        # one's, for a model of one kind)
        self.pages_by_kind = pages_of_kinds(num_pages, self.kinds)
        num_pages = self.pages_by_kind[0]
        if min(self.pages_by_kind) < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is the NULL page), "
                f"got {self.pages_by_kind}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.dtype = dtype
        self.quant = quant
        self.layers = layers
        self.caches = init_page_pool_caches(
            num_layers, self.pages_by_kind, page_size, num_kv_heads,
            head_dim, dtype, quant=quant, layers=layers, kinds=self.kinds)
        # bytes ONE token's cells take of the device across the layers that
        # keep pages, as the device lays those arrays out
        # (:func:`laid_out_bytes`; a page's scalar parameters as they are):
        # what :attr:`page_bytes` counts by shape, a token, where no lane
        # of a page is padding
        entries = [e for k, e in zip(
            layers.kinds if layers is not None else ("pages",) * num_layers,
            self.caches) if k in PAGED_KINDS]
        self.page_bytes_per_token = sum(
            laid_out_bytes(x.shape[1:], x.dtype) if x.ndim > 2
            else x.dtype.itemsize for e in entries for x in e) / page_size

    @property
    def page_bytes(self) -> int:
        """HBM bytes one page costs across all layers (k + v, plus the
        per-page scale/zero params under int8 quantization — honest
        accounting: the quantized pool pays for its metadata).  With several
        page kinds: the first kind's (:attr:`page_bytes_by_kind` has each)."""
        return self.page_bytes_by_kind[0]

    @property
    def page_bytes_by_kind(self) -> Tuple[int, ...]:
        """Bytes a page of each kind costs across that kind's layers."""
        if len(self.kinds) == 1:
            return (_page_bytes(self.num_layers, self.page_size,
                                self.num_kv_heads, self.head_dim, self.dtype,
                                self.quant, self.layers),)
        return tuple(_page_bytes(self.kinds.layers(k), self.page_size,
                                 self.num_kv_heads, self.head_dim, self.dtype,
                                 None, None) for k in range(len(self.kinds)))

    @property
    def state_bytes(self) -> int:
        """HBM bytes of the recurrent layers' state rows (0 without any)."""
        return (self.layers.state_rows * self.layers.state_row_bytes
                if self.layers is not None else 0)

    @property
    def total_bytes(self) -> int:
        return sum(n * b for n, b in zip(
            self.pages_by_kind, self.page_bytes_by_kind)) + self.state_bytes

    @staticmethod
    def pages_for_budget(budget_bytes: int, num_layers: int, page_size: int,
                         num_kv_heads: int, head_dim: int,
                         dtype: Any = jnp.bfloat16,
                         quant: Optional[str] = None,
                         layers: Optional[LayerStates] = None) -> int:
        """How many pool pages a given HBM budget buys — the sizing half of
        the paged-vs-contiguous comparison (a contiguous ``[B, T]`` cache's
        budget is ``B * T / page_size`` pages).  ``quant="int8"`` roughly
        doubles the answer at a fixed budget versus bf16 (1 byte/element +
        four fp32 page params instead of 2 bytes/element)."""
        per_page = _page_bytes(num_layers, page_size, num_kv_heads, head_dim,
                               dtype, quant, layers)
        if layers is not None:
            # the state rows come off the budget first: they are there
            # whatever the pages hold
            budget_bytes -= layers.state_rows * layers.state_row_bytes
        if not per_page:
            return 0    # no layer keeps a page: none to buy
        return max(int(budget_bytes // per_page), 0)


def _page_bytes(num_layers, page_size, num_kv_heads, head_dim, dtype, quant,
                layers: Optional[LayerStates]) -> int:
    """Bytes one page costs across the layers that HAVE pages: K and V, the
    int8 page params, the compressed keys of a block-sparse layer; a latent
    layer's ``page * latent_dim`` elements."""
    from neuronx_distributed_tpu.kvcache.quant import page_layer_bytes

    per_layer = page_layer_bytes(page_size, num_kv_heads, head_dim, quant,
                                 dtype)
    if layers is None:
        return num_layers * per_layer
    comp = (layers.comp_slots * num_kv_heads * head_dim
            * jnp.dtype(dtype).itemsize)
    latent = page_size * layers.latent_dim * jnp.dtype(dtype).itemsize
    return sum(per_layer + (comp if k == "selected_pages" else 0)
               for k in layers.kinds if k in ("pages", "selected_pages")
               ) + latent * sum(k == "latent" for k in layers.kinds)
