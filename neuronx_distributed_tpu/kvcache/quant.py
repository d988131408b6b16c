"""Int8 page quantization for the paged KV cache (KIVI, Liu et al. 2024:
KV tensors tolerate low-bit quantization with bounded logit drift).

A quantized page pool stores each ``[NKV, page, D]`` page as int8 plus ONE
fp32 ``(scale, zero)`` pair per page (asymmetric affine: ``x ≈ (q + 128) *
scale + zero``), halving the HBM a page costs versus bf16 — the pool holds
~2x the pages at a fixed budget, and HBM (not compute) is what caps serving
concurrency (PR 5's measured result).  The quantization granularity is the
PAGE — the same unit the allocator refcounts — so quantize-on-write happens
exactly where page writes already happen (the block-table scatter of a
prefill chunk, a decode token or a verify chunk) and dequantize-in-the-gather reproduces
the same ``[B, T]`` view the band-mask attention core consumes, leaving
the attention math untouched.

Error model: an asymmetric 8-bit page has max absolute error
``(max - min) / 255 / 2`` — :func:`quant_error_bound` is the per-page bound
the parity-tolerance tests assert against (exact equality is the WRONG
test for a lossy cache; a bounded-drift regression threshold is the right
one).  Two exactness cases fall out of the affine form: an all-constant
page round-trips exactly (``scale == 0``, ``zero`` carries the value — the
zero decode tail never drifts), and so does any two-valued page.

Pure jnp helpers for the model's scatter/gather path; no engine state
lives here.
"""

from __future__ import annotations

import jax.numpy as jnp

# registry counter: pages written through a quantize-on-write path
QUANT_PAGES_TOTAL = "kvcache/quant_pages_total"

# int8 codes span [-128, 127]; the affine form uses the unsigned view
_LEVELS = 255.0
_OFFSET = 128.0


def quantize_page(x):
    """Quantize pages over their trailing three axes (``[NKV, page, D]`` in
    the pool; the statistics are order-blind).

    ``x`` is ``[..., NKV, page, D]`` float; returns ``(q int8, scale fp32,
    zero fp32)`` with ``scale``/``zero`` shaped like the leading axes.
    Asymmetric affine per page: ``zero = min(x)``, ``scale = (max - min) /
    255``; an all-constant page gets ``scale == 0`` and round-trips
    exactly through ``zero``."""
    xf = x.astype(jnp.float32)
    mn = jnp.min(xf, axis=(-3, -2, -1))
    mx = jnp.max(xf, axis=(-3, -2, -1))
    scale = (mx - mn) / _LEVELS
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.round((xf - mn[..., None, None, None]) / safe[..., None, None, None])
    q = jnp.clip(q, 0.0, _LEVELS) - _OFFSET
    return q.astype(jnp.int8), scale, mn


def dequantize_page(q, scale, zero, dtype=jnp.float32):
    """Invert :func:`quantize_page`: ``q`` is ``[..., NKV, page, D]`` int8,
    ``scale``/``zero`` its leading-axes fp32 params."""
    xf = (q.astype(jnp.float32) + _OFFSET) * scale[..., None, None, None] \
        + zero[..., None, None, None]
    return xf.astype(dtype)


def quant_error_bound(x) -> float:
    """Max absolute round-trip error the affine page code permits for the
    given page content: half a quantization step, ``(max - min) / 255 / 2``
    (plus fp32 rounding slack).  The parity-tolerance tests assert the
    observed drift under this bound instead of demanding exact equality."""
    import numpy as np

    xf = np.asarray(x, np.float32)
    return float((xf.max() - xf.min()) / _LEVELS / 2.0 + 1e-6)


def page_layer_bytes(page_size: int, num_kv_heads: int, head_dim: int,
                     quant: str | None, dtype) -> int:
    """HBM bytes ONE page costs for ONE layer's k+v under the given layout:
    the fp pool pays ``2 * page * NKV * D * itemsize``; the int8 pool pays
    1 byte per element plus four fp32 page params (k/v scale + zero) — the
    honest per-page accounting :meth:`PagePool.pages_for_budget` sizes
    with."""
    elems = page_size * num_kv_heads * head_dim
    if quant is None:
        return 2 * elems * jnp.dtype(dtype).itemsize
    if quant != "int8":
        raise ValueError(f"unknown KV quantization {quant!r} "
                         "(supported: 'int8')")
    return 2 * elems * 1 + 4 * 4  # int8 payload + (ks, kz, vs, vz) fp32
