#!/usr/bin/env python
"""granite_aot.py — compile the paged decode and chunk-prefill programs of a
Granite-4.0-H configuration (Mamba-2 layers beside attention layers whose
heads are 64 wide, a dense SwiGLU in every layer) at REAL size for a
described (not attached) ``v5e:2x2``, in the sandbox, at no chip time.

    JAX_PLATFORMS=cpu python benchmarks/tools/granite_aot.py --workload <cell> [--layers N]

``nemotron_aot.py``'s compile, with the pool built as THIS program lays a
K/V page out (``kvcache.pool.page_layout`` where the program has it: heads
of 64 two to a 128-lane row; a program without it keeps ``[pages, kv heads,
page, 64]``).  Prints, beside ``memory_analysis()`` of each program:

- the pool's bytes as the arrays' shapes give them and as the DEVICE lays
  them out — the tiled layout of the program's pool parameter, read from the
  compiled text — and both over the tokens the pool holds: a head of 64
  alone in its lane row reads twice its bytes there;
- whether the text holds a copy shaped like a pool or a state array (none:
  both are donated and updated in place);
- how many arrays of the scan's block shape ``[1, c, c, heads]`` the chunk
  program keeps (``ops/ssm_scan.py::_block``: the decay mask and the
  repeated ``C B^T`` of a block of ``c`` rows).

``--layers N`` keeps the first N entries of the layer lists."""

import argparse
import math
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

GIB = 2.0 ** 30


def page_shape(cfg, num_pages, page_size):
    """One K (or V) pool array's shape as this program builds it."""
    try:
        from neuronx_distributed_tpu.kvcache.pool import page_layout
    except ImportError:     # a program older than the paired layout
        heads, width = cfg.num_kv_heads, cfg.head_dim_
    else:
        heads, width = page_layout(cfg.num_kv_heads, cfg.head_dim_)
    return (num_pages, heads, page_size, width)


def abstract_pool(model, num_pages, page_size, mesh):
    """``nemotron_aot.abstract_pool`` with the page array's shape asked of
    the program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_tpu.kvcache.pool import LayerStates

    cfg = model.module.config
    layers = LayerStates.for_config(cfg, page_size, model.config.batch_size)
    rep = NamedSharding(mesh, P())
    page = jax.ShapeDtypeStruct(page_shape(cfg, num_pages, page_size),
                                model.config.kv_cache_dtype, sharding=rep)
    state = tuple(jax.ShapeDtypeStruct((layers.state_rows,) + shape,
                                       jnp.dtype(dt), sharding=rep)
                  for shape, dt in layers.state_arrays)
    entry = {"state": state, "pages": (page, page), "none": ()}
    return tuple(entry[k] for k in layers.kinds), (page,) + state


def laid_out_bytes(text, sds):
    """``(bytes, layout)`` of an array of ``sds``'s shape as the compiled
    program's parameters hold it: its tiled layout ``{...:T(r,c)(..)}`` pads
    the two minor dimensions to whole tiles."""
    dims = ",".join(str(d) for d in sds.shape)
    m = re.search(r"\w+\[" + re.escape(dims) + r"\]\{([^}]*)\} parameter\(",
                  text)
    if m is None:
        return None, None
    layout = m.group(1)
    tile = re.search(r"T\((\d+),(\d+)\)", layout)
    rows, cols = (int(tile.group(1)), int(tile.group(2))) if tile else (1, 1)
    *lead, r, c = sds.shape
    nbytes = (math.prod(lead) * -(-r // rows) * rows * -(-c // cols) * cols
              * sds.dtype.itemsize)
    return nbytes, layout


def compile_serve_programs(cell, layers=None):
    """``nemotron_aot.compile_serve_programs`` over THIS file's pool."""
    from benchmarks.tools import nemotron_aot

    theirs = nemotron_aot.abstract_pool
    nemotron_aot.abstract_pool = abstract_pool
    try:
        return nemotron_aot.compile_serve_programs(cell, layers)
    finally:
        nemotron_aot.abstract_pool = theirs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args()

    from benchmarks.harness import manifest
    from benchmarks.tools.aot_compile import report
    from benchmarks.tools.sala_aot import pool_copies

    cell = manifest.Cell(args.workload)
    s = cell.config["serving"]
    programs, nbytes, pool_bytes, shapes, mcfg = compile_serve_programs(
        cell, args.layers)
    kinds = mcfg.mixer_types
    page, states = shapes[0], shapes[1:]
    n_attn = kinds.count("attention")
    tokens = (s["num_pages"] - 1) * s["page_size"]
    plain = 2 * n_attn * page.size * page.dtype.itemsize
    state_bytes = kinds.count("mamba2") * sum(
        x.size * x.dtype.itemsize for x in states)
    print(f"[aot] {cell.name}: {mcfg.num_layers} layers "
          f"({kinds.count('mamba2')} mamba2, {n_attn} attention); weights "
          f"{nbytes / GIB:.3f} GiB; K/V pages {plain / GIB:.3f} GiB by shape "
          f"({s['num_pages']} pages of {s['page_size']}, arrays "
          f"{list(page.shape)}), state rows {state_bytes / GIB:.3f} GiB "
          f"({s['slots']} rows)")
    totals = []
    for name, compiled in programs:
        totals.append(report(name, compiled))
        text = compiled.as_text()
        laid, layout = laid_out_bytes(text, page)
        if laid is not None:
            laid *= 2 * n_attn
            print(f"[aot] {name}: a pool array {list(page.shape)} is laid "
                  f"out as {{{layout}}}: K/V pages {laid / GIB:.3f} GiB on "
                  f"the device, {laid / (s['num_pages'] * s['page_size']):.0f}"
                  f" bytes a token ({plain / (s['num_pages'] * s['page_size']):.0f}"
                  " by shape)")
        copies = pool_copies(text, shapes)
        print(f"[aot] {name}: {len(copies)} pool- or state-shaped copies"
              + "".join("\n      " + c for c in copies[:6]))
        c = mcfg.ssm_chunk_rows
        block = re.findall(r"= (\w+)\[(?:1,)?%d,%d,%d\]\S* (\w+)\(" % (
            c, c, mcfg.ssm_heads), text)
        kinds_of = sorted({f"{dt} {op}" for dt, op in block})
        calls = sorted(set(re.findall(
            r"%(paged_attention_\w+|kv_pool_write)", text)))
        print(f"[aot] {name}: {len(block)} array(s) of the scan's block "
              f"shape [{c}, {c}, {mcfg.ssm_heads}] are results in the text "
              f"({', '.join(kinds_of) or 'none'}); named Mosaic calls "
              f"{calls}", flush=True)
    print(f"[aot] resident weights + pool {(nbytes + pool_bytes) / GIB:.2f} "
          f"GiB by shape over {tokens} tokens of pages; largest program "
          f"total {max(totals) / GIB:.2f} GiB")


if __name__ == "__main__":
    main()
