#!/usr/bin/env python
"""xing4_aot.py — compile the paged decode and chunk-prefill programs of a
configuration whose attention layers keep pages of LATENTS (``mixer_types``
``"mla"``) at REAL size for a described (not attached) ``v5e:2x2``, in the
sandbox, at no chip time.

    JAX_PLATFORMS=cpu python benchmarks/tools/xing4_aot.py --workload <cell> [--layers N]

``nemotron_aot.py`` beside it builds K/V pages and state rows; this one
builds what ``kvcache.pool.LayerStates`` describes for a latent layer — one
``[NP, page, latent_dim]`` array, no K/V pair.  Prints ``memory_analysis()``
for each program and whether its text holds a copy shaped like the pool
(there should be none: it is donated and updated in place) or like a slot's
expanded K/V.  ``--layers N`` keeps the first N entries of the layer lists
(1 dense + N - 1 expert layers)."""

import argparse
import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

GIB = 2.0 ** 30


def compile_serve_programs(cell, layers=None):
    """``[(name, compiled)]`` of the two serve programs, with the bytes of
    the weights and of the pool, and the shapes no copy may have."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness import common
    from benchmarks.tools.aot_compile import abstract_params
    from neuronx_distributed_tpu.kvcache.pool import LayerStates
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
    states = LayerStates.for_config(model_cfg, s["page_size"], s["slots"])
    rep = NamedSharding(mesh, P())
    dt = model.config.kv_cache_dtype
    pool = jax.ShapeDtypeStruct(
        (s["num_pages"], s["page_size"], states.latent_dim), dt, sharding=rep)
    assert set(states.kinds) == {"latent"}, states.kinds
    caches = tuple((pool,) for _ in states.kinds)
    pool_bytes = len(caches) * pool.size * pool.dtype.itemsize
    T, NH = s["max_total_len"], model_cfg.num_heads
    # what no program may copy: the pool, and a slot's keys or values expanded
    shapes = (pool,
              jax.ShapeDtypeStruct((T, NH, model_cfg.qk_nope_head_dim), dt),
              jax.ShapeDtypeStruct((1, T, NH, model_cfg.qk_nope_head_dim), dt),
              jax.ShapeDtypeStruct((NH, T, model_cfg.qk_nope_head_dim), dt))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)  # noqa: E731
    B, PP, W = s["slots"], T // s["page_size"], s["prefill_chunk_tokens"]
    out = []
    for name, rows, update in (("paged decode", B, True),
                               ("paged chunk prefill", 1, False)):
        fn = jax.jit(functools.partial(
            model._paged_step_fn, paged_kernel=True, update_valid=update,
            last_only=True), donate_argnums=(4,))
        kw = {} if update else {"last_row": i32()}
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
    print(f"[aot] {cell.name}: {mcfg.num_layers} layers "
          f"({len(mcfg.latent_layers)} latent attention, "
          f"{len(mcfg.moe_layers)} routed, {mcfg.hc_mult} streams); weights "
          f"{nbytes / GIB:.2f} GiB; latent pages {pool_bytes / GIB:.2f} GiB "
          f"({s['num_pages']} pages of {s['page_size']} rows of "
          f"{mcfg.latent_row_dim})")
    totals = []
    for name, compiled in programs:
        totals.append(report(name, compiled))
        copies = pool_copies(compiled.as_text(), shapes)
        print(f"[aot] {name}: {len(copies)} pool- or K/V-shaped copies"
              + "".join("\n      " + c for c in copies[:6]), flush=True)
    print(f"[aot] resident weights + pool {(nbytes + pool_bytes) / GIB:.2f} "
          f"GiB; largest program total {max(totals) / GIB:.2f} GiB")


if __name__ == "__main__":
    main()
