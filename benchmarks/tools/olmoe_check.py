#!/usr/bin/env python
"""olmoe_check.py — logits AND routing of the paged path against ``olmoe_f32``
at published widths, on the chip; and what the written tolerance catches.

    chiprun --timeout 1500 -- python benchmarks/tools/olmoe_check.py [--faults]

Builds the cell's model as the runner does, runs the configuration's probe
(prompts 100 / 400 / 760 + 2 decodes) through the paged programs
(``harness/routed_check.py``) and prints one JSON line a prompt: the
relative logit error, the share of (row, layer) expert choices that agree,
and how many disagreements the rounding allowance accepted and refused.
``--faults`` then serves the SAME programs weights the reference does not
see — the expert matrices rounded to e4m3, and to int8 with one scale an
expert — and prints the same lines: how far below each of them the
faithful program's error sits is how tight the tolerance is.  ``--block``
runs one expert block alone, faithful and with both roundings (seconds
after the weights): where the experts are not diluted by a seeded model's
residual stream, an 8-bit matmul shows.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def round_to(kind):
    """Expert matrices as an 8-bit matmul would see them."""
    import jax.numpy as jnp

    def fix(x):
        if kind == "e4m3":
            # e4m3's 3 mantissa bits, by arithmetic (the chip's compiler
            # folds a convert to float8 and back into nothing)
            m, e = jnp.frexp(x.astype(jnp.float32))
            return jnp.ldexp(jnp.round(m * 16) / 16, e).astype(x.dtype)
        scale = jnp.max(jnp.abs(x.astype(jnp.float32)),
                        axis=tuple(range(1, x.ndim)), keepdims=True) / 127
        return (jnp.round(x.astype(jnp.float32) / scale) * scale
                ).astype(x.dtype)
    return fix


def expert_block(cfg, params, ref_mod, shape, ref_w, seed):
    """The program's expert block of layer 0 ALONE on 512 seeded rows of
    unit root mean square (what the block's norm hands it), against the
    reference's ``experts`` on the same rows: no residual stream dilutes the
    experts here and no later router amplifies a flip, so an 8-bit expert
    matmul shows (``tolerances.expert_block_rel``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import check, common

    _, model_cfg = common.program_config(cfg["program"])
    from neuronx_distributed_tpu.parallel.moe import ExpertParallelMLP

    moe = ExpertParallelMLP(
        num_experts=model_cfg.num_experts,
        intermediate_size=model_cfg.intermediate_size,
        top_k=model_cfg.moe_top_k, dispatch="dropless",
        norm_topk_prob=model_cfg.moe_norm_topk_prob, fused_gate_up=False,
        dtype=model_cfg.dtype, param_dtype=model_cfg.param_dtype)
    lp = params["params"]["model"]["layer_0"]["moe_mlp"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 3),
                          (512, model_cfg.hidden_size)).astype(model_cfg.dtype)
    lw = next(iter(ref_w["layers"]))

    @jax.jit
    def reference(x):
        with jax.default_matmul_precision("highest"):
            return ref_mod.experts(x.astype(jnp.float32), lw, shape)[0]

    want = np.asarray(reference(x))
    run = jax.jit(lambda p, x: moe.apply({"params": p}, x)[0])
    for name in ("faithful", "e4m3-experts", "int8-experts"):
        served = lp if name == "faithful" else {
            k: (v if k == "router" else jax.jit(round_to(name.split("-")[0]))(v))
            for k, v in lp.items()}
        got = np.asarray(run(served, x), np.float32)
        rms = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
        print(json.dumps({
            "served": name, "what": "expert block of layer 0, 512 rows",
            "rel_err": check.rel_err(got, want), "rms_err": rms,
            "within_tolerance": rms <= cfg["tolerances"]["expert_block_rms"]}),
            flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="olmoe-1b-7b.serve-backlog")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--block", action="store_true",
                    help="only the expert block of layer 0, alone")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import common, manifest, routed_check, serve_runner
    from neuronx_distributed_tpu.obs.compile_ledger import CompileLedger

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    devices, _ = common.check_devices(cell, args.rehearse)
    cfg = cell.config
    params, model = serve_runner.build(cell, args, devices, CompileLedger())
    nd = cfg["probe"]["decodes"]
    rs = np.random.RandomState(args.seed + 7)
    seqs = [rs.randint(1, cfg["vocab_size"], size=n + nd).astype(np.int32)
            for n in cfg["probe"]["prompt_lens"]]
    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    ref_w = cell.reference_weights(params)
    sigmas = cfg["tolerances"]["routing_sigmas"]

    if args.block:
        return expert_block(cfg, params, ref_mod, shape, ref_w, args.seed)
    refs = routed_check.reference(ref_mod, ref_w, shape, seqs)

    def is_expert(path):
        name = jax.tree_util.keystr(path)
        return "moe_mlp" in name and "router" not in name

    # the weights fill the chip: a fault is served IN PLACE, a leaf at a
    # time, from the host's copy of the faithful expert matrices
    kept = {}
    if args.faults:
        jax.tree_util.tree_map_with_path(
            lambda path, x: kept.__setitem__(jax.tree_util.keystr(path),
                                             np.asarray(x))
            if is_expert(path) else None, params)

    def serve(kind):
        def leaf(path, x):
            if not is_expert(path):
                return x
            sharding = x.sharding
            x.delete()
            return jax.jit(round_to(kind), donate_argnums=0)(jax.device_put(
                kept[jax.tree_util.keystr(path)], sharding))
        return jax.tree_util.tree_map_with_path(leaf, model.params)

    rows = []
    for name in ["faithful"] + (["e4m3-experts", "int8-experts"]
                                if args.faults else []):
        if name != "faithful":
            model.params = serve(name.split("-")[0])
        logits_at, choices = routed_check.paged_logits_and_choices(
            model, cfg["serving"], seqs, nd)
        for verdict in routed_check.compare(ref_mod, refs, nd, logits_at,
                                            choices, sigmas):
            row = {"served": name, **verdict, "within_tolerance": (
                verdict["logits_rel_err"] <= cfg["tolerances"]["logits_rel"]
                and not verdict["refused"])}
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/olmoe_check.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
