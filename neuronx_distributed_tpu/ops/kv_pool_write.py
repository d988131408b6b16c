"""The page pool's one writer: a step's new K/V rows, committed IN PLACE.

The pool is head-major ``[NP, NKV, page, D]`` (``kvcache.pool``: the layout
the paged kernel's copies read; heads of 64 lie two to a 128-lane row,
``[NP, NKV / 2, page, 128]``, and the writer is the same at that shape).  A scatter of single rows whose two indices
(page, cell) are split by the head axis made the chip's compiler relay the
whole pool out for the scatter and back for the kernel — two pool-sized
copies a pool a layer a program, for a write of a few rows (PERF.md, PR 28).
So the write is PAGE-granular, on the pool's leading axis only: the pages a
slot's rows touch are read, the new rows are selected into them, and the
pages are written back where they lay.  A page of all its kv heads is one
contiguous ``NKV * page * D`` slab, so nothing about the pool's layout is
asked to change and the donated buffer is updated in place.

Two forms of the same write, bit for bit:

- XLA (every platform, the gather path, tp > 1 under GSPMD): a gather of the
  touched pages, a select, a scatter on the leading axis;
- a Pallas call named ``kv_pool_write`` (where the paged kernel runs): the
  pool stays in HBM, aliased input to output; the touched pages' ids are
  scalar-prefetched; a program copies a block of pages in (one
  ``make_async_copy`` a page for all its heads — the walk kernel's pattern
  turned round), selects the new rows, copies the pages out.  A row routed
  to ``NP`` starts no copy.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.ops.flash_attention import (
    _compiler_params,
    run_kernel,
)
from neuronx_distributed_tpu.ops.paged_attention import kv_head_tp_mesh
from neuronx_distributed_tpu.parallel.mesh import TENSOR_AXIS

# VMEM one program's page buffer may take (its block of new rows is the same
# size, double-buffered by the pipeline)
_BUF_BYTES = 2 ** 20


def touched_pages(new, phys, in_off, num_pages: int, page: int):
    """Group a write's rows by the pool page they land in.

    Row ``s`` of slot ``b`` is cell ``in_off[b, 0] + s`` of the slot's chain
    from the page of its first row on (consecutive cells: a decode token, a
    verify or prefill chunk), so the ``Sn`` rows of a slot touch at most
    ``n = ceil((Sn - 1) / page) + 1`` pages.  Returns ``(pj [B * n], hot
    [B * n, page], ins [B * n, NKV, page, D])``: each touched page's
    physical id (``num_pages`` where none of its cells is written: dropped),
    which of its cells are written, and the rows that go there, head-major
    like a pool page.  A row whose ``phys`` lies outside ``[0, num_pages)``
    is not written."""
    B, Sn = phys.shape
    n = (Sn - 1 + page - 1) // page + 1
    s = jnp.arange(n * page, dtype=jnp.int32)[None, :] - in_off[:, :1]
    sc = jnp.clip(s, 0, Sn - 1)                       # [B, n * page]
    ph = jnp.take_along_axis(phys, sc, axis=1)
    hot = (s >= 0) & (s < Sn) & (ph >= 0) & (ph < num_pages)
    pj = jnp.min(jnp.where(hot, ph, num_pages).reshape(B, n, page), axis=2)
    ins = jnp.take_along_axis(new, sc[:, :, None, None], axis=1)
    ins = ins.reshape(B, n, page, *new.shape[2:]).transpose(0, 1, 3, 2, 4)
    return (pj.reshape(B * n).astype(jnp.int32), hot.reshape(B * n, page),
            ins.reshape(B * n, *ins.shape[2:]))


def _write_kernel(pj_ref, ins_ref, hot_ref, pool_in, pool_out, buf, sem, *,
                  block, num_pages):
    """One program: ``block`` touched pages read, the new rows selected in,
    the pages written back.  ``pool_in`` and ``pool_out`` are the one HBM
    buffer (aliased)."""
    base = pl.program_id(0) * block

    def for_each_page(fn):
        def body(j, carry):
            phys = pj_ref[base + j]

            @pl.when(phys < num_pages)
            def _():
                fn(j, phys)

            return carry

        jax.lax.fori_loop(0, block, body, 0)

    def read(j, phys):
        return pltpu.make_async_copy(pool_in.at[phys], buf.at[j], sem.at[0])

    def write(j, phys):
        return pltpu.make_async_copy(buf.at[j], pool_out.at[phys], sem.at[1])

    for_each_page(lambda j, p: read(j, p).start())
    for_each_page(lambda j, p: read(j, p).wait())
    buf[...] = jnp.where(hot_ref[...][:, None] != 0, ins_ref[...], buf[...])
    for_each_page(lambda j, p: write(j, p).start())
    for_each_page(lambda j, p: write(j, p).wait())


def _write_pages_kernel(pool, pj, hot, ins, interpret):
    NP, NKV, page, D = pool.shape
    N = pj.shape[0]
    block = max(1, min(N, _BUF_BYTES // (NKV * page * D * pool.dtype.itemsize)))
    pad = -N % block
    if pad:
        pj = jnp.pad(pj, (0, pad), constant_values=NP)
        hot = jnp.pad(hot, ((0, pad), (0, 0)))
        ins = jnp.pad(ins, ((0, pad), (0, 0), (0, 0), (0, 0)))
    # the cells' mask as lanes of the pool's own dtype: the select's three
    # operands then share one tiling
    hot = jnp.broadcast_to(hot[:, :, None], (*hot.shape, D)).astype(pool.dtype)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((N + pad) // block,),
        in_specs=[
            pl.BlockSpec((block, NKV, page, D), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((block, page, D), lambda i, *_: (i, 0, 0)),
            any_space,
        ],
        out_specs=any_space,
        scratch_shapes=[pltpu.VMEM((block, NKV, page, D), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    kernel = functools.partial(_write_kernel, block=block, num_pages=NP)

    def call(interp):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            # operands count the scalar-prefetched ids: the pool is the 4th
            input_output_aliases={3: 0},
            # pages are written by one slot only, but a program reads what
            # its predecessor may have written to a page it shares: in order
            compiler_params=_compiler_params(("arbitrary",), interp),
            interpret=interp,
            name="kv_pool_write",
        )

    return run_kernel(call, interpret, pj, ins, hot, pool)


def _write_pages_xla(pool, pj, hot, ins):
    pages = pool[jnp.clip(pj, 0, pool.shape[0] - 1)]
    pages = jnp.where(hot[:, None, :, None], ins, pages)
    return pool.at[pj].set(pages, mode="drop")


# jitted like ``_paged_attention_impl``: a serve program calls it twice a
# layer with the same shapes, and traces (and lowers) it once
@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def _write_pool_rows_impl(pool, new, phys, in_off, kernel=False,
                          interpret=None):
    NP, _, page, _ = pool.shape
    pj, hot, ins = touched_pages(new.astype(pool.dtype), phys, in_off, NP, page)
    if kernel:
        return _write_pages_kernel(pool, pj, hot, ins, interpret)
    return _write_pages_xla(pool, pj, hot, ins)


def write_pool_rows(pool: jax.Array, new: jax.Array, phys: jax.Array,
                    in_off: jax.Array, *, kernel: bool = False,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Commit ``new [B, Sn, NKV, D]`` into ``pool [NP, NKV, page, D]``: row
    ``(b, s)`` goes to cell ``(phys[b, s], :, in_off[b, s])``, and a row
    whose ``phys`` is ``NP`` (a parked slot, a row past the table's end, a
    pad row whose validity is 0) is dropped.  The rows of a slot are
    CONSECUTIVE cells of its chain (:func:`touched_pages`), and no page is
    written by two slots (decode pages are a slot's own).  Every other cell
    of the pool keeps its bits; given the pool donated, the write is in
    place.  Where the pool is ``[NP, NKV / 2, page, 2D]`` (heads of 64, two
    to a lane row) the rows are laid the same way first.

    ``kernel`` takes the Pallas call (the caller's resolved ``paged_kernel``:
    where the paged kernel runs, so does this), else the XLA form; both
    leave the same bits.  ``interpret`` as in ``ops.paged_attention``."""
    # a pool that keeps heads of half a lane row two to a row
    # (``kvcache.pool.page_layout``) takes the rows' heads side by side too:
    # the plain reshape, and then the same write at the pool's width
    new = new.reshape(*new.shape[:2], pool.shape[1], pool.shape[3])
    write = functools.partial(_write_pool_rows_impl, kernel=kernel,
                              interpret=interpret)
    mesh = kv_head_tp_mesh(pool.shape[1]) if kernel else None
    if mesh is not None:
        # the paged kernel's wrap: each shard writes its own heads of the
        # touched pages; the cells are replicated
        write = jax.shard_map(
            write, mesh=mesh,
            in_specs=(P(None, TENSOR_AXIS, None, None),
                      P(None, None, TENSOR_AXIS, None), P(None, None),
                      P(None, None)),
            out_specs=P(None, TENSOR_AXIS, None, None), check_vma=False)
    return write(pool, new, phys.astype(jnp.int32), in_off.astype(jnp.int32))
