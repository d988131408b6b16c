#!/usr/bin/env python
"""train_routed_aot.py — compile the train step of a ``train_routed`` cell at
REAL size for a described (not attached) ``v5e:2x2``, in the sandbox, at no
chip time, and print its bytes a device.

    JAX_PLATFORMS=cpu python benchmarks/tools/train_routed_aot.py --workload <cell> [--batch N]

``aot_compile.py`` beside it does the same for ``train`` cells; this one
builds the optimizer as ``initialize_parallel_optimizer`` does for a model
with state leaves (a router bias gets no moment), and ``--batch`` overrides
the traffic mix's batch: it is how the batch of the cell was chosen.  A
compile is not a run and says nothing about time."""

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness import common, manifest
    from benchmarks.tools.aot_compile import abstract_params, report
    from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
    from neuronx_distributed_tpu.optimizer.adamw_fp32 import (
        adamw_fp32,
        build_lr_schedule,
    )
    from neuronx_distributed_tpu.optimizer.zero1 import optimizer_state_specs
    from neuronx_distributed_tpu.parallel.mesh import get_mesh
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec,
        make_train_step,
    )
    from neuronx_distributed_tpu.trainer.trainer import (
        ParallelModel,
        ParallelOptimizer,
        _is_state_leaf,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    cell = manifest.Cell(args.workload)
    cfg, mix, tr = cell.config, cell.traffic, cell.config["training"]
    seq, batch = mix["seq_len"], args.batch or mix["batch"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    tp = cfg["layout"]["tensor_parallel_size"]
    nxd.initialize_model_parallel(devices=topo.devices[:cell.chips],
                                  tensor_parallel_size=tp)
    mesh = get_mesh()
    config = nxd.training_config(
        learning_rate=tr["learning_rate"],
        zero_one_enabled=tr["zero_one_enabled"],
        compute_dtype=tr["compute_dtype"], param_dtype=tr["param_dtype"],
        warmup_steps=tr.get("warmup_steps", 0), seed=0,
        tensor_parallel_size=tp)
    module_cls, model_cfg = common.program_config(
        {**cfg["program"],
         "kwargs": {**cfg["program"]["kwargs"], "max_seq_len": seq}})
    module = module_cls(model_cfg)
    params, specs = abstract_params(module, mesh,
                                    jnp.zeros((1, seq), jnp.int32))
    model = ParallelModel(module=module, params=params, param_specs=specs,
                          mesh=mesh)
    print(f"[aot] {cell.name}: {model.num_parameters() / 1e6:.0f}M "
          f"parameters, batch {batch} x {seq}")
    oc = config.optimizer
    labels = jax.tree_util.tree_map_with_path(
        lambda p, _: "freeze" if _is_state_leaf(jax.tree_util.keystr(p))
        else "train", params)
    tx = optax.multi_transform(
        {"train": adamw_fp32(
            build_lr_schedule(oc.learning_rate, oc.lr_schedule,
                              oc.warmup_steps, oc.total_steps,
                              oc.min_lr_ratio),
            b1=oc.beta1, b2=oc.beta2, eps=oc.eps,
            weight_decay=oc.weight_decay),
         "freeze": optax.set_to_zero()}, labels)
    state_struct = jax.eval_shape(tx.init, params)
    state_specs = optimizer_state_specs(
        state_struct, params, specs, zero1=oc.zero_one_enabled, mesh=mesh)
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
        state_struct, state_specs)
    opt = ParallelOptimizer(
        tx=tx, state=state, state_specs=state_specs, mesh=mesh,
        update_mask=jax.tree.map(lambda l: l == "train", labels))
    step = make_train_step(
        config, model, opt,
        make_causal_lm_loss_sum(chunk_size=tr["loss_chunk"]),
        batch_spec={"ids": default_batch_spec(),
                    "labels": default_batch_spec()})
    bsh = NamedSharding(mesh, default_batch_spec())
    b = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=bsh)
         for k in ("ids", "labels")}
    report(f"train step, batch {batch}",
           step.lower(params, state, b, None).compile())


if __name__ == "__main__":
    main()
