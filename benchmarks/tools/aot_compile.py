#!/usr/bin/env python
"""aot_compile.py — compile a cell's step programs at REAL size for a
described (not attached) ``v5e:2x2``, in the sandbox, at no chip time.

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_compile.py --workload <cell> [--layers N]

Prints ``memory_analysis()`` per device for each program: the train step, or
the paged chunk-prefill and decode programs of a serve cell (weights and
pool are arguments, so their bytes are in ``argument_size``).  What the
chip's compiler refuses here it would refuse there.  ``--layers`` overrides
the configuration's depth: it is how the depth of a cut is chosen.  A
compile is not a run and says nothing about time.

It reaches into the program (``ParallelModel`` built from abstract
parameters, ``ParallelInferenceModel._paged_step_fn``) because the normal
path places real arrays on real devices; nothing under ``harness/`` does.
"""

import argparse
import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

GIB = 2.0 ** 30


def report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    text = compiled.as_text()
    print(f"[aot] {name}: per device arguments "
          f"{m.argument_size_in_bytes / GIB:.2f} GiB, outputs "
          f"{m.output_size_in_bytes / GIB:.2f}, aliased "
          f"{m.alias_size_in_bytes / GIB:.2f}, temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f} -> total {total / GIB:.2f} GiB; "
          f"{text.count('tpu_custom_call')} Mosaic call(s), collectives: "
          + ", ".join(f"{op} x{text.count(' ' + op + '(')}" for op in
                      ("all-gather", "all-reduce", "reduce-scatter",
                       "collective-permute", "all-gather-start",
                       "all-reduce-start", "collective-permute-start")),
          flush=True)
    return total


def abstract_params(module, mesh, *example):
    import jax
    from flax import linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    boxed = jax.eval_shape(module.init, jax.random.PRNGKey(0), *example)
    specs = nn.get_partition_spec(boxed)
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        nn.unbox(boxed), specs)
    return params, specs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--slots", type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness import common, manifest
    from neuronx_distributed_tpu.parallel.mesh import get_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    cell = manifest.Cell(args.workload)
    cfg = cell.config
    kwargs = dict(cfg["program"]["kwargs"])
    if args.layers:
        kwargs["num_layers"] = args.layers
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = topo.devices[:cell.chips]
    tp = cfg["layout"]["tensor_parallel_size"]
    nxd.initialize_model_parallel(devices=devices, tensor_parallel_size=tp)
    mesh = get_mesh()
    print(f"[aot] {cell.name}: {kwargs['num_layers']} layers on "
          f"{len(devices)} described device(s), tp={tp}")

    if cfg["runner"] == "train":
        from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
        from neuronx_distributed_tpu.optimizer.zero1 import (
            optimizer_state_specs,
        )
        from neuronx_distributed_tpu.optimizer.adamw_fp32 import (
            adamw_fp32,
            build_lr_schedule,
        )
        from neuronx_distributed_tpu.trainer import (
            default_batch_spec,
            make_train_step,
        )
        from neuronx_distributed_tpu.trainer.trainer import (
            ParallelModel,
            ParallelOptimizer,
        )

        mix, tr = cell.traffic, cfg["training"]
        seq, batch = mix["seq_len"], mix["batch"]
        config = nxd.training_config(
            learning_rate=tr["learning_rate"],
            zero_one_enabled=tr["zero_one_enabled"],
            compute_dtype=tr["compute_dtype"], param_dtype=tr["param_dtype"],
            seed=0, tensor_parallel_size=tp)
        module_cls, model_cfg = common.program_config(
            {**cfg["program"], "kwargs": {**kwargs, "max_seq_len": seq}})
        module = module_cls(model_cfg)
        params, specs = abstract_params(
            module, mesh, jnp.zeros((1, seq), jnp.int32))
        model = ParallelModel(module=module, params=params,
                              param_specs=specs, mesh=mesh)
        print(f"[aot] {model.num_parameters() / 1e6:.0f}M parameters")
        oc = config.optimizer
        tx = adamw_fp32(
            build_lr_schedule(oc.learning_rate, oc.lr_schedule,
                              oc.warmup_steps, oc.total_steps,
                              oc.min_lr_ratio),
            b1=oc.beta1, b2=oc.beta2, eps=oc.eps,
            weight_decay=oc.weight_decay)
        state_struct = jax.eval_shape(tx.init, params)
        state_specs = optimizer_state_specs(
            state_struct, params, specs, zero1=oc.zero_one_enabled, mesh=mesh)
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            state_struct, state_specs)
        opt = ParallelOptimizer(tx=tx, state=state, state_specs=state_specs,
                                mesh=mesh)
        bspec = {"ids": default_batch_spec(), "labels": default_batch_spec()}
        step = make_train_step(
            config, model, opt,
            make_causal_lm_loss_sum(chunk_size=tr["loss_chunk"]),
            batch_spec=bspec)
        bsh = NamedSharding(mesh, default_batch_spec())
        b = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=bsh)
             for k in ("ids", "labels")}
        report("train step", step.lower(params, state, b, None).compile())
        return

    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    s = dict(cfg["serving"])
    if args.slots:
        s["slots"] = args.slots
    module_cls, model_cfg = common.program_config(
        {**cfg["program"], "kwargs": {**kwargs,
                                      "max_seq_len": s["max_total_len"]}})
    module = module_cls(model_cfg)
    params, _ = abstract_params(
        module, mesh, jnp.zeros((1, s["page_size"]), jnp.int32))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    print(f"[aot] weights {nbytes / GIB:.2f} GiB")
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=s["slots"], context_len=s["context_len"],
                        max_total_len=s["max_total_len"],
                        kv_cache_dtype=getattr(jnp, s["kv_cache_dtype"])))
    L, NKV, D = kwargs["num_layers"], kwargs["num_kv_heads"], kwargs["head_dim"]
    rep = NamedSharding(mesh, P())
    page = jax.ShapeDtypeStruct(
        (s["num_pages"], NKV, s["page_size"], D), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, "tp", None, None)))
    caches = tuple((page, page) for _ in range(L))
    pool_bytes = L * 2 * page.size * 2
    print(f"[aot] pool {pool_bytes / GIB:.2f} GiB ({s['num_pages']} pages)")
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)  # noqa: E731
    B, T, PP, W = (s["slots"], s["max_total_len"],
                   s["max_total_len"] // s["page_size"],
                   s["prefill_chunk_tokens"])
    decode = jax.jit(functools.partial(
        model._paged_step_fn, paged_kernel=True, update_valid=True,
        last_only=True), donate_argnums=(4,))
    t1 = report("paged decode", decode.lower(
        params, i32(B, 1), i32(B), i32(B, PP), caches, i32(B, T)).compile())
    chunk = jax.jit(functools.partial(
        model._paged_step_fn, paged_kernel=True, update_valid=False,
        last_only=True), donate_argnums=(4,))
    t2 = report("paged chunk prefill", chunk.lower(
        params, i32(1, W), i32(1), i32(1, PP), caches, i32(1, T),
        last_row=i32()).compile())
    print(f"[aot] resident weights + pool {(nbytes + pool_bytes) / GIB:.2f} "
          f"GiB; largest program total {max(t1, t2) / GIB:.2f} GiB")


if __name__ == "__main__":
    main()
