#!/usr/bin/env python
"""sala_aot.py — compile the paged decode and chunk-prefill programs of a
configuration with a layer LIST (``mixer_types``: K/V pages for the softmax
layers, compressed keys, state rows) at REAL size for a described (not
attached) ``v5e:2x2``, in the sandbox, at no chip time.

    JAX_PLATFORMS=cpu python benchmarks/tools/sala_aot.py --workload <cell> [--layers N]

``aot_compile.py`` beside it builds a pool of one page shape a layer; this
one builds the pool the layer list describes (``kvcache.pool.LayerStates``).
Prints ``memory_analysis()`` for each program and whether its text holds a
copy shaped like a pool array or the state array (there should be none: the
pool and the state rows are donated and updated in place).  ``--layers N``
keeps the first N entries of the layer list."""

import argparse
import functools
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

GIB = 2.0 ** 30


def abstract_pool(model, num_pages, page_size, mesh):
    """The pool's pytree as ``ShapeDtypeStruct``s (nothing is placed)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_tpu.kvcache.pool import LayerStates

    cfg = model.module.config
    layers = LayerStates.for_config(cfg, page_size, model.config.batch_size)
    rep = NamedSharding(mesh, P())
    dt = model.config.kv_cache_dtype
    page = jax.ShapeDtypeStruct(
        (num_pages, cfg.num_kv_heads, page_size, cfg.head_dim_), dt,
        sharding=rep)
    comp = jax.ShapeDtypeStruct(
        (num_pages, layers.comp_slots, cfg.num_kv_heads, cfg.head_dim_), dt,
        sharding=rep)
    state = jax.ShapeDtypeStruct(
        (layers.state_rows,) + layers.state_shape, jnp.float32, sharding=rep)
    entry = {"state": (state,), "selected_pages": (page, page, comp),
             "pages": (page, page)}
    return tuple(entry[k] for k in layers.kinds), (page, comp, state)


def pool_copies(text, shapes):
    """Lines of the compiled text that copy an array shaped like the pool's."""
    found = []
    for sds in shapes:
        dims = ",".join(str(d) for d in sds.shape)
        pat = re.compile(r"= \w+\[" + re.escape(dims) + r"\][^ ]* copy\(")
        found += [ln.strip()[:120] for ln in text.splitlines()
                  if pat.search(ln)]
    return found


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness import common, manifest
    from benchmarks.tools.aot_compile import abstract_params, report
    from neuronx_distributed_tpu.parallel.mesh import get_mesh
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    cell = manifest.Cell(args.workload)
    cfg, s = cell.config, cell.config["serving"]
    kwargs = dict(cfg["program"]["kwargs"])
    if args.layers:
        kwargs["num_layers"] = args.layers
        kwargs["mixer_types"] = kwargs["mixer_types"][:args.layers]
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
    print(f"[aot] {cell.name}: {kwargs['num_layers']} layers "
          f"({kwargs['mixer_types'].count('minicpm4')} minicpm4, "
          f"{kwargs['mixer_types'].count('lightning-attn')} lightning-attn); "
          f"weights {nbytes / GIB:.2f} GiB")
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=s["slots"], context_len=s["context_len"],
                        max_total_len=s["max_total_len"],
                        kv_cache_dtype=getattr(jnp, s["kv_cache_dtype"])))
    caches, shapes = abstract_pool(model, s["num_pages"], s["page_size"], mesh)
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(caches))
    print(f"[aot] pool + compressed keys + state rows "
          f"{pool_bytes / GIB:.2f} GiB ({s['num_pages']} pages of "
          f"{s['page_size']}, {s['slots']} state rows)")
    rep = NamedSharding(mesh, P())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)  # noqa: E731
    B, T, PP, W = (s["slots"], s["max_total_len"],
                   s["max_total_len"] // s["page_size"],
                   s["prefill_chunk_tokens"])
    totals = []
    for name, rows, update in (("paged decode", B, True),
                               ("paged chunk prefill", 1, False)):
        fn = jax.jit(functools.partial(
            model._paged_step_fn, paged_kernel=True, update_valid=update,
            last_only=True), donate_argnums=(4,))
        kw = {} if update else {"last_row": i32()}
        compiled = fn.lower(
            params, i32(rows, 1 if update else W), i32(rows), i32(rows, PP),
            caches, i32(rows, T), state_rows=i32(rows), **kw).compile()
        totals.append(report(name, compiled))
        copies = pool_copies(compiled.as_text(), shapes)
        print(f"[aot] {name}: {len(copies)} pool- or state-shaped copies"
              + "".join("\n      " + c for c in copies[:6]), flush=True)
    print(f"[aot] resident weights + pool {(nbytes + pool_bytes) / GIB:.2f} "
          f"GiB; largest program total {max(totals) / GIB:.2f} GiB")


if __name__ == "__main__":
    main()
