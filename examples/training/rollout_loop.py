#!/usr/bin/env python
"""Rollout → train → swap: co-located generation and training with live
in-memory weight swaps — the RLHF-shaped serving/training loop with NO
checkpoint round-trip and NO engine restart.

One process owns both sides:

- a ``ServingEngine`` (continuous batching over a compiled
  ``ParallelInferenceModel``) generates rollouts — greedy continuations of
  a fixed prompt set under the CURRENT weights;
- ``fit()`` trains on those rollouts (self-distillation: the model learns
  to sharpen its own top-1 continuations, so the loss falls);
- every ``--swap-every`` optimizer steps a :class:`Callback.on_params`
  hook hands the LIVE param pytree to ``WeightSwapper.swap(...,
  source="memory")`` — the engine's weights advance mid-flight, no phase
  program recompiles (the compile ledger pins zero post-warmup rows), and
  the next rollout round generates under the NEW version.

The swap copies (host round-trip): the jitted train step donates its
param buffers, so the engine must own its bytes — see
``weights/swapper.py``.

Smoke on the single-device CPU mesh (~30 s):

  JAX_PLATFORMS=cpu python examples/training/rollout_loop.py \
      --steps 24 --swap-every 8

Emits fit()'s per-step JSON lines, one ``{"event": "swap", ...}`` line
per live swap, and a final summary line with ``loss_fell``, ``swaps``,
``post_warmup_compiles`` (must be 0) and the per-round rollout weight
versions (proving outputs flip to the new version exactly at the swap
boundary).
"""

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=24, help="optimizer steps")
    p.add_argument("--swap-every", type=int, default=8,
                   help="live-swap (and re-rollout) cadence in steps")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--prompt-len", type=int, default=8,
                   help="prompt tokens per rollout (== engine context len)")
    p.add_argument("--rollout-tokens", type=int, default=8,
                   help="greedy tokens generated per rollout")
    p.add_argument("--rollout-requests", type=int, default=12,
                   help="rollouts per round (served over --serve-slots)")
    p.add_argument("--serve-slots", type=int, default=4,
                   help="engine batch size (continuous-batching slots)")
    p.add_argument("--batch-size", type=int, default=8,
                   help="training batch size (rows sampled per step)")
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--swaps-out", default=None,
                   help="weight_swaps.jsonl audit-trail path")
    p.add_argument("--metrics-file", default=None, help="JSON results file")
    p.add_argument("--virtual-devices", type=int, default=None)
    return p.parse_args()


def main():
    args = parse_args()
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
        make_causal_lm_loss_sum,
    )
    from neuronx_distributed_tpu.obs import MetricRegistry
    from neuronx_distributed_tpu.obs.compile_ledger import CompileLedger
    from neuronx_distributed_tpu.serving import Request, ServingEngine
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )
    from neuronx_distributed_tpu.trainer import (
        Callback,
        default_batch_spec,
        fit,
        initialize_parallel_model,
        initialize_parallel_optimizer,
    )
    from neuronx_distributed_tpu.utils import initialize_distributed
    from neuronx_distributed_tpu.utils.common import ensure_virtual_devices
    from neuronx_distributed_tpu.weights import WeightSwapper

    if args.virtual_devices:
        ensure_virtual_devices(args.virtual_devices)
    initialize_distributed()
    nxd.initialize_model_parallel(tensor_parallel_size=args.tp)

    P, M = args.prompt_len, args.rollout_tokens
    S = P + M  # training rows are exactly one prompt + its rollout
    config = nxd.training_config(
        tensor_parallel_size=args.tp,
        learning_rate=args.lr,
        lr_schedule="cosine",
        warmup_steps=2,
        total_steps=max(args.steps, 3),
        compute_dtype="float32",
        param_dtype="float32",
        seed=args.seed,
    )
    cfg = LlamaConfig.tiny(
        max_seq_len=S, sequence_parallel=False, remat="none",
        dtype=config.jnp_compute_dtype, param_dtype=config.jnp_param_dtype)
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg),
        (jnp.zeros((1, S), jnp.int32),), seed=args.seed)
    opt = initialize_parallel_optimizer(config, model)
    loss_fn = make_causal_lm_loss_sum()

    # the serving side: its OWN module instance (inference-tuned apply:
    # no remat, no SP) over an independent COPY of the initial params —
    # fit()'s first donated step would otherwise invalidate the engine's
    # version-0 buffers
    icfg_model = LlamaConfig.tiny(
        max_seq_len=S, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32)
    infer_params = jax.tree.map(
        lambda x: jax.device_put(np.asarray(x)), model.params)
    infer = ParallelInferenceModel(
        LlamaForCausalLM(icfg_model), infer_params,
        InferenceConfig(batch_size=args.serve_slots, context_len=P,
                        max_total_len=S, kv_cache_dtype=jnp.float32))
    ledger = CompileLedger()
    # a page must divide the prompt and the row; the pool defaults to every
    # slot holding a whole row
    engine = ServingEngine(infer, registry=MetricRegistry(),
                           page_size=math.gcd(P, M), compile_ledger=ledger)
    swapper = WeightSwapper(engine, path=args.swaps_out)

    rs = np.random.RandomState(args.seed)
    prompts = [rs.randint(1, cfg.vocab_size, size=P).tolist()
               for _ in range(args.rollout_requests)]
    rid_counter = [0]
    round_versions = []  # [(round, min_version, max_version)] per rollout

    def rollout_round():
        """Generate one greedy continuation per prompt under the engine's
        CURRENT weights; returns [N, S] rows of prompt + rollout."""
        for p in prompts:
            rid_counter[0] += 1
            engine.submit(Request(request_id=rid_counter[0], prompt_ids=p,
                                  max_new_tokens=M))
        outs = engine.run_until_complete(max_steps=1000)
        rows, versions = [], []
        by_id = {o.request_id: o for o in outs}
        base = rid_counter[0] - len(prompts)
        for i, p in enumerate(prompts):
            o = by_id[base + 1 + i]
            rows.append(p + list(o.token_ids))
            versions.append(o.weights_version)
        round_versions.append(
            (len(round_versions), min(versions), max(versions)))
        return np.asarray(rows, np.int32)

    buffer = {"rows": rollout_round()}  # round 0: version-0 weights
    # every phase program this loop ever needs (prefill, decode, slot
    # reuse) just compiled: one post-warmup ledger row from here on is a
    # regression, and a swap must add none
    engine.declare_warmup_done()

    # loss only over the GENERATED tokens (labels at P-1 .. S-2): the
    # rollout is the model's own top-1 stream — sharpening it is the
    # learnable part; the random prompt tokens are irreducible noise
    row_mask = np.zeros((args.batch_size, S), np.float32)
    row_mask[:, P - 1:S - 1] = 1.0
    row_mask = jnp.asarray(row_mask)

    def next_batch(step):
        rows = buffer["rows"]
        sel = np.random.RandomState(args.seed * 1000 + step).randint(
            0, rows.shape[0], size=args.batch_size)
        ids = jnp.asarray(rows[sel])
        return {"ids": ids, "labels": jnp.roll(ids, -1, axis=1),
                "mask": row_mask}

    class SwapCallback(Callback):
        """Every --swap-every steps: live-swap the trainer's params into
        the engine (in-memory, copied), then refresh the rollout buffer
        under the new version."""

        def __init__(self):
            self.swaps = []
            self.losses = []

        def on_step(self, step, metrics):
            self.losses.append(float(metrics["loss"]))

        def on_params(self, step, params, opt_state):
            if (step + 1) % args.swap_every or step + 1 >= args.steps:
                return
            mark = ledger.mark()
            version = swapper.swap(params, source="memory")
            compiles = ledger.compiles_since(mark)
            buffer["rows"] = rollout_round()
            self.swaps.append({"step": step + 1, "version": version,
                               "swap_compiles": compiles})
            print(json.dumps({"event": "swap", "step": step + 1,
                              "version": version,
                              "swap_compiles": compiles}), flush=True)

    cb = SwapCallback()
    bspec = {"ids": default_batch_spec(), "labels": default_batch_spec(),
             "mask": default_batch_spec()}
    res = fit(config, model, opt, next_batch, steps=args.steps,
              loss_fn=loss_fn, batch_spec=bspec, callbacks=[cb],
              log_every=max(args.swap_every // 2, 1))

    engine.close()
    swapper.close()
    head = float(np.mean(cb.losses[:3])) if cb.losses else float("nan")
    summary = {
        "event": "summary",
        "steps": res.steps_run,
        "first_loss": round(head, 4),
        "final_loss": round(res.final_loss, 4),
        "loss_fell": bool(res.final_loss < head),
        "swaps": len(cb.swaps),
        "versions": [s["version"] for s in cb.swaps],
        "post_warmup_compiles": ledger.compile_count(after_warmup_only=True),
        "rollout_rounds": len(round_versions),
        # (round, min, max): min == max per round — every rollout in a
        # round decoded under exactly one weights_version, and the version
        # steps up by one per swap
        "rollout_versions": round_versions,
    }
    print(json.dumps(summary), flush=True)
    if args.metrics_file:
        with open(args.metrics_file, "w") as f:
            json.dump(summary, f)
    ok = (summary["loss_fell"] and summary["swaps"] >= 2
          and summary["post_warmup_compiles"] == 0
          and all(lo == hi for _, lo, hi in round_versions))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
