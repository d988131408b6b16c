#!/usr/bin/env python
"""nemotron_aot.py — compile the paged decode and chunk-prefill programs of a
configuration whose layer list holds Mamba-2 layers, attention layers and
layers that keep nothing (``mixer_types`` / ``ffn_types``) at REAL size for a
described (not attached) ``v5e:2x2``, in the sandbox, at no chip time.

    JAX_PLATFORMS=cpu python benchmarks/tools/nemotron_aot.py --workload <cell> [--layers N]

``sala_aot.py`` beside it builds the pool of a layer list with ONE float32
state array; this one builds whatever ``kvcache.pool.LayerStates`` describes
(a tuple of state arrays a recurrent layer, ``()`` for a layer without a
mixer).  Prints ``memory_analysis()`` for each program and whether its text
holds a copy shaped like a pool or a state array (there should be none: both
are donated and updated in place).  ``--layers N`` keeps the first N entries
of the layer lists."""

import argparse
import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

GIB = 2.0 ** 30


def abstract_pool(model, num_pages, page_size, mesh):
    """The pool's pytree as ``ShapeDtypeStruct``s (nothing is placed), and
    one of each shape in it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_tpu.kvcache.pool import LayerStates

    cfg = model.module.config
    layers = LayerStates.for_config(cfg, page_size, model.config.batch_size)
    rep = NamedSharding(mesh, P())
    page = jax.ShapeDtypeStruct(
        (num_pages, cfg.num_kv_heads, page_size, cfg.head_dim_),
        model.config.kv_cache_dtype, sharding=rep)
    state = tuple(jax.ShapeDtypeStruct((layers.state_rows,) + shape,
                                       jnp.dtype(dt), sharding=rep)
                  for shape, dt in layers.state_arrays)
    entry = {"state": state, "pages": (page, page), "none": ()}
    return tuple(entry[k] for k in layers.kinds), (page,) + state


def compile_serve_programs(cell, layers=None):
    """``[(name, compiled)]`` of the two serve programs, with the bytes of
    the weights and of the pool."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness import common
    from benchmarks.tools.aot_compile import abstract_params
    from neuronx_distributed_tpu.parallel.mesh import get_mesh
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    cfg, s = cell.config, cell.config["serving"]
    kwargs = dict(cfg["program"]["kwargs"])
    if layers:
        kwargs.update(num_layers=layers,
                      mixer_types=kwargs["mixer_types"][:layers],
                      ffn_types=kwargs["ffn_types"][:layers])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    nxd.initialize_model_parallel(devices=topo.devices[:1],
                                  tensor_parallel_size=1)
    mesh = get_mesh()
    module_cls, model_cfg = common.program_config(
        {**cfg["program"], "kwargs": {**kwargs,
                                      "max_seq_len": s["max_total_len"]}})
    module = module_cls(model_cfg)
    params, _ = abstract_params(
        module, mesh, jnp.zeros((1, s["page_size"]), jnp.int32))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=s["slots"], context_len=s["context_len"],
                        max_total_len=s["max_total_len"],
                        kv_cache_dtype=getattr(jnp, s["kv_cache_dtype"])))
    caches, shapes = abstract_pool(model, s["num_pages"], s["page_size"], mesh)
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(caches))
    rep = NamedSharding(mesh, P())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)  # noqa: E731
    B, T, PP, W = (s["slots"], s["max_total_len"],
                   s["max_total_len"] // s["page_size"],
                   s["prefill_chunk_tokens"])
    out = []
    for name, rows, update in (("paged decode", B, True),
                               ("paged chunk prefill", 1, False)):
        fn = jax.jit(functools.partial(
            model._paged_step_fn, paged_kernel=True, update_valid=update,
            last_only=True), donate_argnums=(4,))
        # as the engine calls them: a decode of every slot steps the state
        # arrays where they lie, a one-row chunk is told its state row
        kw = {} if update else {"last_row": i32(), "state_rows": i32(rows)}
        out.append((name, fn.lower(
            params, i32(rows, 1 if update else W), i32(rows), i32(rows, PP),
            caches, i32(rows, T), **kw).compile()))
    return out, nbytes, pool_bytes, shapes, model_cfg


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
    print(f"[aot] {cell.name}: {mcfg.num_layers} layers "
          f"({kinds.count('mamba2')} mamba2, {kinds.count('attention')} "
          f"attention, {len(mcfg.moe_layers)} routed); weights "
          f"{nbytes / GIB:.2f} GiB; pages + state rows "
          f"{pool_bytes / GIB:.2f} GiB ({s['num_pages']} pages of "
          f"{s['page_size']}, {s['slots']} state rows)")
    totals = []
    for name, compiled in programs:
        totals.append(report(name, compiled))
        copies = pool_copies(compiled.as_text(), shapes)
        print(f"[aot] {name}: {len(copies)} pool- or state-shaped copies"
              + "".join("\n      " + c for c in copies[:6]), flush=True)
    print(f"[aot] resident weights + pool {(nbytes + pool_bytes) / GIB:.2f} "
          f"GiB; largest program total {max(totals) / GIB:.2f} GiB")


if __name__ == "__main__":
    main()
