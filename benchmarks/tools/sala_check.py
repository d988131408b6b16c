#!/usr/bin/env python
"""sala_check.py — the kernel path against the gather path ON THE CHIP, at a
cell's serving geometry, through a THIN model of the same mixers.

    python benchmarks/tools/sala_check.py --workload minicpm-sala.serve-longdocs

The cell's reference check holds the whole served model to the float32
reference; when it fails it does not say which layer kind did.  This tool
keeps every shape the two mixers' kernels see — heads, head size, page,
chunk width, slots, context, the sparse_config — and shrinks what they do
not (hidden 512, MLP 1024, vocabulary 1024, three layers: minicpm4,
lightning-attn, minicpm4), so that set-up is seconds.  It runs the probe
(chunked prefill through one-row programs told their state row, then
decodes of all rows) twice, with ``paged_kernel`` True (the Pallas walks:
``sparse_attention_chunk`` under its mask, ``sparse_attention_decode`` over
its chosen table, ``kv_pool_write``) and False (the chain gathered into a
``[B, T]`` view under the same mask), and prints, per prompt, the relative
difference of the logits of the last prompt position and of each decode, and
whether both paths chose the same pages.  Both paths run bfloat16: a
difference of a few 1e-3 is rounding; more is a kernel's.
"""

import argparse
import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def full_model(cell, seed, args):
    import copy
    import dataclasses
    import gc
    import types

    import jax.numpy as jnp

    import jax

    from benchmarks.harness import serve_runner, serve_state_runner
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    cell = copy.copy(cell)
    cell.config = {**cell.config, "probe": {
        **cell.config["probe"],
        **({"prompt_lens": [int(x) for x in args.lens.split(",")]}
           if args.lens else {}),
        **({"decodes": args.decodes} if args.decodes else {})}}
    params, model = serve_runner.build(
        cell, types.SimpleNamespace(seed=seed), jax.devices()[:1], None)
    module = model.module
    f32 = type(module)(dataclasses.replace(module.config, dtype=jnp.float32))
    coarse = copy.copy(cell)
    coarse.reference_weights = lambda p: _rounded(cell.reference_weights(p))
    for key, name, mod, kernel, held in (
            ("kernels", "kernels, bfloat16", module, True, cell),
            ("gather", "gather path, bfloat16", module, False, cell),
            ("f32", "kernels, float32 activations", f32, True, cell),
            ("e4m3", "kernels, bfloat16, against the reference on weights "
             "rounded to float8_e4m3", module, True, coarse),
            ("bf16state", "kernels, bfloat16, the lightning state rounded "
             "to bfloat16 as a call reads and leaves it", module, True,
             cell)):
        if key not in args.variants.split(","):
            continue
        print(f"[sala_check] --- {name}", flush=True)
        m = ParallelInferenceModel(
            mod, params, model.config,
            paged_kernel=kernel and not args.rehearse)
        with _bf16_state(key == "bf16state"):
            for line in serve_state_runner.reference_check(held, params, m,
                                                           seed):
                print(f"[sala_check] not correct: {line}", flush=True)
        del m
        gc.collect()


@contextlib.contextmanager
def _bf16_state(on: bool):
    """The control of ``tolerances.state_rel``: the program's lightning
    core with its state rounded to bfloat16 on the way in and out of every
    block of rows (what a pool that keeps bfloat16 state rows would do)."""
    import jax.numpy as jnp

    from neuronx_distributed_tpu.ops import lightning_attention as la

    block = la._block

    def rounded(state, *a):
        st, o = block(state.astype(jnp.bfloat16).astype(jnp.float32), *a)
        return st.astype(jnp.bfloat16).astype(jnp.float32), o

    if on:
        la._block = rounded
    try:
        yield
    finally:
        la._block = block


class _RoundedLayers:
    """The reference's layers with every matrix rounded through
    float8_e4m3 as it is handed over (a layer at a time: no second copy)."""

    def __init__(self, layers):
        self._layers = layers

    def __len__(self):
        return len(self._layers)

    def __iter__(self):
        return (_round_tree(lw) for lw in self._layers)


def _round_tree(tree):
    import jax.numpy as jnp

    return {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                if getattr(v, "ndim", 0) >= 2 else v)
            for k, v in tree.items()}


def _rounded(weights):
    return {**_round_tree({k: v for k, v in weights.items()
                           if k != "layers"}),
            "layers": _RoundedLayers(weights["layers"])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--lens", default=None,
                    help="prompt lengths (default: the cell's probe)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--decodes", type=int, default=None,
                    help="decoded rows of the reference comparison "
                         "(default: the cell's probe.decodes)")
    ap.add_argument("--variants", default="kernels,gather,f32,e4m3",
                    help="--full: which of kernels (the served program), "
                         "gather (its gather path), f32 (float32 "
                         "activations), e4m3 (the served program against "
                         "the reference reading the weights rounded to "
                         "float8_e4m3: the nearest precision below), "
                         "bf16state (the served program with its lightning "
                         "state rounded to bfloat16: the control of "
                         "tolerances.state_rel)")
    ap.add_argument("--precise", action="store_true",
                    help="also: the thin model in float32 with matmuls at "
                         "'highest' precision (the gather path: the walk "
                         "kernel takes no float32 queries) against the "
                         "reference — what is left is not rounding")
    ap.add_argument("--full", default=None, metavar="SEED",
                    help="instead: the cell's OWN model (every layer, the "
                         "run's weights of this seed) through the cell's "
                         "reference check three times — kernels, the gather "
                         "path, kernels with float32 activations — to tell "
                         "a kernel's fault from rounding's")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import check, common, manifest
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    cfg, s = cell.config, cell.config["serving"]
    kw = dict(cfg["program"]["kwargs"])
    if args.full is not None:
        return full_model(cell, int(args.full), args)
    thin = dict(hidden_size=min(kw["hidden_size"], 512),
                intermediate_size=min(kw["intermediate_size"], 1024),
                vocab_size=min(kw["vocab_size"], 1024), num_layers=3,
                mixer_types=["minicpm4", "lightning-attn", "minicpm4"],
                max_seq_len=s["max_total_len"])
    module_cls, mcfg = common.program_config(
        {**cfg["program"], "kwargs": {**kw, **thin}})
    module = module_cls(mcfg)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, s["page_size"]), jnp.int32))
    page, C, T, B = (s["page_size"], s["context_len"], s["max_total_len"],
                     s["slots"])
    W, PP, nd = s["prefill_chunk_tokens"], T // page, 2
    lens = ([int(x) for x in args.lens.split(",")] if args.lens
            else cfg["probe"]["prompt_lens"])
    rs = np.random.RandomState(7)
    seqs = [rs.randint(1, thin["vocab_size"], size=L + nd).astype(np.int32)
            for L in lens]
    from benchmarks.harness import serve_state_runner

    def probe(kernel):
        model = ParallelInferenceModel(
            module, params,
            InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                            kv_cache_dtype=getattr(jnp, s["kv_cache_dtype"])),
            paged_kernel=kernel)
        return serve_state_runner.probe(model, {**s, "num_pages": 2}, seqs,
                                        lens, nd)

    kern, kern_pick, _ = probe(True)
    gath, gath_pick, _ = probe(False)
    worst = 0.0
    for b, L in enumerate(lens):
        errs = [check.rel_err(kern[(b, j)], gath[(b, j)])
                for j in range(nd + 1)]
        same = [int((kern_pick[(b, j)] != gath_pick[(b, j)]).any(-1).sum())
                for j in range(nd + 1)]
        worst = max(worst, *errs)
        print(f"[sala_check] prompt {L} (last chunk {(C - (C - L) // page * page - 1) % W + 1} rows): "
              "kernels vs gather, rel diff prefill "
              f"{errs[0]:.4f}, decodes " + " ".join(f"{e:.4f}" for e in errs[1:])
              + f"; (layer, kv head) sets chosen differently: {same}",
              flush=True)
    print(f"[sala_check] worst {worst:.4f}")

    # the same thin model against the float32 reference, by the cell's own
    # check (blocks chosen held to the reference's, logits with the
    # reference attending the program's blocks)
    import copy

    thin_cell = copy.copy(cell)
    thin_cell.config = manifest.deep_merge(cfg, {
        "hidden_size": thin["hidden_size"],
        "intermediate_size": thin["intermediate_size"],
        "vocab_size": thin["vocab_size"], "num_hidden_layers": 3,
        "dim_model_base": thin["hidden_size"] * kw.get("logit_scale", 1.0),
        "probe": {"prompt_lens": lens,
                  "decodes": args.decodes or nd},
        "serving": {"num_pages": 2}})
    thin_cell.config["mixer_types"] = thin["mixer_types"]
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=getattr(jnp, s["kv_cache_dtype"])),
        paged_kernel=not args.rehearse)
    for line in serve_state_runner.reference_check(thin_cell, params, model,
                                                   0):
        print(f"[sala_check] NOT CORRECT: {line}")
    if args.precise:
        import dataclasses

        print("[sala_check] --- float32, matmuls at 'highest' precision",
              flush=True)
        exact = module_cls(dataclasses.replace(mcfg, dtype=jnp.float32))
        model = ParallelInferenceModel(
            exact, params,
            InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                            kv_cache_dtype=jnp.float32),
            paged_kernel=False)
        with jax.default_matmul_precision("highest"):
            serve_state_runner.reference_check(thin_cell, params, model, 0)


if __name__ == "__main__":
    main()
