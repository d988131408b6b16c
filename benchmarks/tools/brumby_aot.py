#!/usr/bin/env python
"""brumby_aot.py — compile the Brumby cell's paged programs at REAL size for
a described (not attached) ``v5e:2x2``, in the sandbox, at no chip time: the
decode and the chunk-prefill program over a pool of state rows alone (two
float32 arrays a layer, no page), their memory, and whether either holds a
copy shaped like a state array (the state must be stepped where it lies:
a second copy of 4.5 GiB does not fit).

    JAX_PLATFORMS=cpu python benchmarks/tools/brumby_aot.py \
        --workload brumby-14b.serve-continuations [--layers N]

``aot_compile.py``'s report and abstract parameters and ``sala_aot.py``'s
search for pool-shaped copies, imported; neither builds a state row of two
arrays.  A compile is not a run and says nothing about time.
"""

import argparse
import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.tools.aot_compile import GIB, abstract_params, report  # noqa: E402
from benchmarks.tools.sala_aot import pool_copies  # noqa: E402


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
    from neuronx_distributed_tpu.kvcache.pool import LayerStates
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
    print(f"[aot] {cell.name}: {kwargs['num_layers']} layers; weights "
          f"{nbytes / GIB:.2f} GiB")
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=s["slots"], context_len=s["context_len"],
                        max_total_len=s["max_total_len"],
                        kv_cache_dtype=getattr(jnp, s["kv_cache_dtype"])))
    layers = LayerStates.for_config(model_cfg, s["page_size"], s["slots"])
    if layers.paged:
        raise SystemExit(f"{cell.name} keeps pages: aot_compile.py or "
                         "sala_aot.py describe its pool")
    rep = NamedSharding(mesh, P())
    row = tuple(jax.ShapeDtypeStruct((layers.state_rows,) + shape,
                                     jnp.dtype(dt), sharding=rep)
                for shape, dt in layers.state_arrays)
    caches = tuple(row for _ in layers.kinds)
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(caches))
    print(f"[aot] state rows {pool_bytes / GIB:.2f} GiB ({s['slots']} rows x "
          f"{len(caches)} layers of " + " + ".join(
              f"{shape} {dt}" for shape, dt in layers.state_arrays) + ")")
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
        copies = pool_copies(compiled.as_text(), row)
        print(f"[aot] {name}: {len(copies)} state-shaped copies"
              + "".join("\n      " + c for c in copies[:6]), flush=True)
    print(f"[aot] resident weights + state rows "
          f"{(nbytes + pool_bytes) / GIB:.2f} GiB; largest program total "
          f"{max(totals) / GIB:.2f} GiB")


if __name__ == "__main__":
    main()
