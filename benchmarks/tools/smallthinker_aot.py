#!/usr/bin/env python
"""smallthinker_aot.py — compile the SmallThinker cell's paged programs at
REAL size for a described (not attached) ``v5e:2x2``, in the sandbox, at no
chip time: the paged decode and chunk-prefill programs over TWO page kinds
(``serving.num_pages``: one count a kind; a layer's pool arrays have its
kind's), and, with ``--mask-only``, over ONE pool in which every layer holds
a slot's whole history — what the same 16 slots would need were the window
only a mask.

    JAX_PLATFORMS=cpu python benchmarks/tools/smallthinker_aot.py \
        --workload smallthinker-21b-a3b.serve-longdocs [--layers N] [--mask-only]

``aot_compile.py``'s report and abstract parameters, imported; it builds one
pool of one page count for every layer and cannot describe this one.  A
compile is not a run and says nothing about time.
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--mask-only", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness import common, manifest
    from neuronx_distributed_tpu.kvcache.pool import page_kinds
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
        kwargs.update(num_layers=args.layers,
                      sliding_window=kwargs["sliding_window"][:args.layers],
                      attn_rope=kwargs["attn_rope"][:args.layers])
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
    print(f"[aot] {cell.name}: {kwargs['num_layers']} layers, weights "
          f"{nbytes / GIB:.2f} GiB")
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=s["slots"], context_len=s["context_len"],
                        max_total_len=s["max_total_len"],
                        kv_cache_dtype=getattr(jnp, s["kv_cache_dtype"])))
    kinds = page_kinds(model_cfg)
    counts = list(s["num_pages"])
    if args.mask_only:
        # every layer keeps a slot's whole history: the first kind's count
        counts = [counts[0]] * len(kinds)
    NKV, D = kwargs["num_kv_heads"], kwargs["head_dim"]

    def pages(n):
        return jax.ShapeDtypeStruct(
            (n, NKV, s["page_size"], D), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(None, "tp", None, None)))

    caches = tuple((pages(counts[k]), pages(counts[k]))
                   for k in kinds.of_layer)
    pool_bytes = sum(2 * c[0].size * 2 for c in caches)
    by_kind = {w: (counts[k], kinds.layers(k))
               for k, w in enumerate(kinds.windows)}
    print(f"[aot] pool {pool_bytes / GIB:.2f} GiB: window -> (pages, layers) "
          f"{by_kind}" + (" [mask only]" if args.mask_only else ""))
    rep = NamedSharding(mesh, P())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)  # noqa: E731
    B, T, PP, W, K = (s["slots"], s["max_total_len"],
                      s["max_total_len"] // s["page_size"],
                      s["prefill_chunk_tokens"], len(kinds))
    decode = jax.jit(functools.partial(
        model._paged_step_fn, paged_kernel=True, update_valid=True,
        last_only=True), donate_argnums=(4,))
    t1 = report("paged decode", decode.lower(
        params, i32(B, 1), i32(B), i32(K, B, PP), caches, i32(B, T)).compile())
    chunk = jax.jit(functools.partial(
        model._paged_step_fn, paged_kernel=True, update_valid=False,
        last_only=True), donate_argnums=(4,))
    t2 = report("paged chunk prefill", chunk.lower(
        params, i32(1, W), i32(1), i32(K, 1, PP), caches, i32(1, T),
        last_row=i32()).compile())
    print(f"[aot] resident weights + pool {(nbytes + pool_bytes) / GIB:.2f} "
          f"GiB; largest program total {max(t1, t2) / GIB:.2f} GiB")


if __name__ == "__main__":
    main()
