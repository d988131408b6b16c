#!/usr/bin/env python
"""tp_ring_probe.py — what hiding a sequence-parallel gather costs and buys, alone.

    chiprun --chips 4 -- python tools/tp_ring_probe.py

On the four chips of a 2x2 host (tp = 4 with sequence parallel), at
``mistral-7b.train-seq8k-tp4``'s shapes (batch 2 x 8192, hidden 4096,
intermediate 14336, bf16 compute over float32 parameters):

- ``links``: ONE ``ppermute`` of a sequence shard ``[2, 2048, 4096]`` (32 MiB)
  to the rank below, alone; two at once in opposite directions; the shard in
  two halves, one each way; two ranks below; the same bytes as one
  ``all_gather``; and the matmul that would stand beside a hop
  (``[4096, 4096] x [4096, 7168]``).
- ``assemble``: gate-up's gather FORWARD alone, three ways: GSPMD's gather
  then one matmul; a hand-written ring of ``tp`` hops under ``shard_map``
  writing each product where it belongs (rank ``r`` multiplies block
  ``(r + i) % tp`` at hop ``i``: a zeroed ``[2, 8192, 7168]`` and four
  ``dynamic_update_slice``); the same ring leaving its products in the order
  they come (what it would cost if nothing had to be put in place).  The
  ring in place LOSES to GSPMD (PERF.md §6, PR 49), which is why
  ``parallel/collective_matmul.py`` cuts along the batch instead.
- ``sites``: q/k/v (local columns 1536: what ``parallel/qkv.py`` cuts) and
  gate-up (7168: cut HERE through ``collective_matmul.in_pieces``, the tree
  leaves it whole because the cut lost in the step), forward alone and
  forward + backward (the loss's VALUE with its gradient: a gradient alone
  leaves a forward matmul dead), left whole against cut in pieces.

Every row is ms a call (host clock around ``block_until_ready``, three calls
queued a sample, the median of ``--samples``), printed as it comes and written
to ``chiprun_out/tp_ring_probe.json``.  A row under ~2 ms holds a few tenths
of launch.  ``--rehearse`` runs toy shapes on four virtual CPU devices (no
number of it is a device number).
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default="links,assemble,sites")
    ap.add_argument("--samples", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        args.samples = 2

    import jax
    import jax.numpy as jnp
    from flax.core import meta
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.parallel import collective_matmul as cm
    from neuronx_distributed_tpu.parallel.layers import (
        ColumnParallelLinear,
        shard_activation,
        trailing_spec,
    )
    from neuronx_distributed_tpu.parallel.mesh import (
        SEQUENCE_AXES,
        TENSOR_AXES,
    )
    from neuronx_distributed_tpu.parallel.qkv import GQAQKVColumnParallelLinear

    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.rehearse:
        raise SystemExit("no TPU: a probe's numbers are the chip's "
                         "(--rehearse runs the control flow on the CPU)")
    if len(devs) < 4:
        raise SystemExit(f"four chips wanted, {len(devs)} here")
    B, S, H, I, NQ, NKV, D = ((2, 64, 32, 64, 8, 4, 4) if args.rehearse
                              else (2, 8192, 4096, 14336, 32, 8, 128))
    tp = 4
    mesh = nxd.initialize_model_parallel(tensor_parallel_size=tp,
                                         devices=devs[:tp])
    print("[probe] the tensor axes' order:",
          [(d.id, getattr(d, "coords", None))
           for d in mesh.devices.reshape(-1)], flush=True)
    rows = []

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*a).compile()
        compile_s = time.perf_counter() - t0
        jax.block_until_ready(compiled(*a))
        samples = []
        for _ in range(args.samples):
            t0 = time.perf_counter()
            outs = [compiled(*a) for _ in range(3)]
            jax.block_until_ready(outs)
            samples.append((time.perf_counter() - t0) / 3 * 1e3)
        rows.append({"name": name, "ms": statistics.median(samples),
                     "ms_min": min(samples), "compile_s": round(compile_s, 1)})
        print("[probe]", json.dumps(rows[-1]), flush=True)

    def put(key, shape, spec, dtype=jnp.bfloat16):
        return jax.jit(lambda k: jax.random.normal(k, shape, dtype),
                       out_shardings=NamedSharding(mesh, spec))(
                           jax.random.PRNGKey(key))

    seq_sharded = P(None, SEQUENCE_AXES, None)
    gathered = P(None, None, TENSOR_AXES)
    x = put(1, (B, S, H), seq_sharded)
    below = [(i, (i - 1) % tp) for i in range(tp)]
    above = [(i, (i + 1) % tp) for i in range(tp)]

    def mapped(body, in_specs=(seq_sharded,), out_specs=seq_sharded):
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    if "links" in args.phases:
        timed("hop_32MiB_one_way",
              mapped(lambda a: lax.ppermute(a, TENSOR_AXES, below)), x)
        timed("hop_32MiB_both_ways",
              mapped(lambda a, b: (lax.ppermute(a, TENSOR_AXES, below),
                                   lax.ppermute(b, TENSOR_AXES, above)),
                     (seq_sharded,) * 2, (seq_sharded,) * 2),
              x, put(2, (B, S, H), seq_sharded))
        timed("hop_16MiB_both_ways",
              mapped(lambda a: jnp.concatenate(
                  [lax.ppermute(a[:1], TENSOR_AXES, below),
                   lax.ppermute(a[1:], TENSOR_AXES, above)])), x)
        timed("hop_32MiB_two_ranks_below",
              mapped(lambda a: lax.ppermute(
                  a, TENSOR_AXES, [(i, (i - 2) % tp) for i in range(tp)])), x)
        timed("all_gather_96MiB",
              mapped(lambda a: lax.all_gather(a, TENSOR_AXES, axis=1,
                                              tiled=True),
                     out_specs=gathered), x)
        timed("matmul_beside_a_hop_4096x4096x7168",
              lambda a, b: jnp.dot(a, b, preferred_element_type=a.dtype),
              put(3, (B * S // tp, H), P()), put(4, (H, I // 2), P()))

    if "assemble" in args.phases:
        w = put(8, (H, I * 2), P(None, TENSOR_AXES))

        def ring(place):
            def body(a, b):
                r, rows_, piece, out = lax.axis_index(TENSOR_AXES), a.shape[1], a, []
                for i in range(tp):
                    nxt = (lax.ppermute(piece, TENSOR_AXES, below)
                           if i + 1 < tp else None)        # in flight ...
                    out.append(jnp.dot(piece, b,            # ... under this
                                       preferred_element_type=a.dtype))
                    piece = nxt
                if not place:
                    return jnp.concatenate(out, axis=1)
                y = jnp.zeros((a.shape[0], rows_ * tp, b.shape[-1]), a.dtype)
                for i, part in enumerate(out):
                    y = lax.dynamic_update_slice_in_dim(
                        y, part, ((r + i) % tp) * rows_, axis=1)
                return y
            return mapped(body, (seq_sharded, P(None, TENSOR_AXES)), gathered)

        def gspmd(a, b):
            y = jnp.dot(a, b, preferred_element_type=a.dtype)
            return lax.with_sharding_constraint(y, NamedSharding(mesh, gathered))
        timed("assemble_gspmd_gather_then_matmul", gspmd, x, w)
        timed("assemble_ring_in_place", ring(True), x, w)
        timed("assemble_ring_as_they_come", ring(False), x, w)

    if "sites" in args.phases:
        rule = cm.GATHER_MIN_WIDTH
        qkv = GQAQKVColumnParallelLinear(
            num_heads=NQ, num_kv_heads=NKV, head_dim=D, sequence_parallel=True)
        gate_up = ColumnParallelLinear(
            features=2 * I, n_fused=2, use_bias=False, sequence_parallel=True)

        def gate_up_in_pieces(p, a):
            # ColumnParallelLinear is not cut in the tree (it lost): the cut
            # is made here, as parallel/qkv.py makes it
            kernel = p["params"]["kernel"].astype(a.dtype)
            return cm.in_pieces(
                lambda a, w: shard_activation(
                    jnp.einsum("...h,hfp->...fp", a, w,
                               preferred_element_type=a.dtype),
                    trailing_spec(a.ndim + 1, last=TENSOR_AXES)),
                cm.GATHER_PIECES, a, kernel)
        sites = {
            "qkv_N%d" % ((NQ + 2 * NKV) * D // tp): (
                qkv, {"whole": (1 << 30, qkv.apply),
                      "cut": (1 if args.rehearse else rule, qkv.apply)}),
            "gate_up_N%d" % (I * 2 // tp): (
                gate_up, {"whole": (rule, gate_up.apply),
                          "cut": (rule, gate_up_in_pieces)}),
        }
        for site, (layer, forms) in sites.items():
            params = meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(0), x))
            for form, (width, apply) in forms.items():
                cm.GATHER_MIN_WIDTH = width

                def loss(p, a, apply=apply):
                    return sum(jnp.sum(jnp.sin(o.astype(jnp.float32)))
                               for o in jax.tree.leaves(apply(p, a)))
                timed(f"site_{site}_forward_{form}",
                      lambda p, a, apply=apply: apply(p, a), params, x)
                timed(f"site_{site}_forward_backward_{form}",
                      jax.value_and_grad(loss, argnums=(0, 1)), params, x)
        cm.GATHER_MIN_WIDTH = rule

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "tp_ring_probe.json"), "w") as f:
        json.dump({"device": devs[0].device_kind, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
