#!/usr/bin/env python
"""Llama pretraining launcher — the framework-native analogue of the
reference's ``tp_zero1_llama2_7b_hf_pretrain.py`` / ``run_llama_nxd.py``
harnesses: TP x SP x DP (+ ZeRO-1) training with checkpoint/resume, the
native token data loader (or synthetic data), throughput/MFU metrics and an
optional host timeline.

Examples
--------
Synthetic smoke on the 8-device CPU mesh:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python examples/training/llama_pretrain.py --preset tiny --tp 2 \
      --steps 20 --batch-size 8 --seq-len 128 --dtype float32

Real corpus (NXDT token file, see neuronx_distributed_tpu.data):

  python examples/training/llama_pretrain.py --preset llama2_7b --tp 8 \
      --data /path/corpus.nxdt --batch-size 64 --seq-len 4096 \
      --ckpt-dir /path/ckpts --resume
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", default="tiny",
                   choices=["tiny", "llama2_7b", "llama2_13b", "llama2_70b", "llama3_8b", "llama31_8b", "qwen2_7b", "mistral_7b", "mixtral_8x7b"])
    p.add_argument("--tp", type=int, default=1, help="tensor parallel degree")
    p.add_argument("--pp", type=int, default=1, help="pipeline parallel degree")
    p.add_argument("--microbatches", type=int, default=1,
                   help="pipeline microbatches (pp>1)")
    p.add_argument("--pp-schedule", default="1f1b",
                   choices=["1f1b", "gpipe", "interleaved"])
    p.add_argument("--virtual-stages", type=int, default=1,
                   help="interleaved virtual stages per pp rank (with "
                        "--pp-schedule interleaved); divides the bubble by ~V")
    p.add_argument("--loss-chunk", type=int, default=0,
                   help="chunked lm-head+CE: compute the loss per N-token "
                        "sequence chunk so [B,S,V] logits never hit HBM "
                        "(0 = off; 512 is a good TPU value)")
    p.add_argument("--cp", type=int, default=1, help="context parallel degree (ring attention)")
    p.add_argument("--kv-multiplier", type=int, default=1,
                   help="KV replication when num_kv_heads < tp")
    p.add_argument("--no-sp", action="store_true", help="disable sequence parallelism")
    p.add_argument("--no-zero1", action="store_true", help="disable ZeRO-1 state sharding")
    p.add_argument("--attention", default="dense", choices=["dense", "flash"])
    p.add_argument("--remat", default="selective", choices=["none", "selective", "full"])
    p.add_argument("--scan-layers", action="store_true",
                   help="lax.scan over the layer stack (constant compile time in depth)")
    p.add_argument("--batch-size", type=int, default=8, help="global batch size")
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup-steps", type=int, default=10)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--data", default=None, help="NXDT token file (synthetic data if unset)")
    p.add_argument("--packed", action="store_true",
                   help="treat --data as an eos-joined document stream: split, "
                        "first-fit pack with segment masking and per-document "
                        "RoPE positions (data.packing) instead of flat chunking")
    p.add_argument("--packed-eos-id", type=int, default=None,
                   help="eos id separating documents in --data (required with --packed)")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--keep-ckpts", type=int, default=3)
    p.add_argument("--ckpt-bf16", action="store_true",
                   help="downcast the model payload to bfloat16 on save "
                   "(half-size checkpoints; optimizer masters stay fp32)")
    p.add_argument("--ckpt-on-signal", action="store_true",
                   help="on SIGTERM/SIGINT, finish the current step, write "
                   "the final checkpoint, and exit cleanly (preemption-safe "
                   "training; pair with --resume on restart)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--metrics-file", default=None, help="JSON results file")
    p.add_argument("--timeline", default=None, help="Chrome-trace output path")
    p.add_argument("--scalar-dir", default=None,
                   help="TensorBoard/JSONL scalar stream dir (designated-process only)")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"],
                   help="compute dtype (master params stay float32); taken "
                        "from here, never from the backend the run finds")
    p.add_argument("--virtual-devices", type=int, default=None,
                   help="force an N-device virtual CPU mesh (dev/test runs)")
    args = p.parse_args()
    if args.ckpt_on_signal and not args.ckpt_dir:
        p.error("--ckpt-on-signal requires --ckpt-dir")
    if args.loss_chunk and args.pp > 1:
        p.error("--loss-chunk has no effect with --pp > 1: the pipeline "
                "engine owns the head+loss (its last stage computes per-"
                "microbatch logits already bounded by the microbatch size)")
    if args.packed and not args.data:
        p.error("--packed requires --data (an eos-joined NXDT document stream)")
    if args.packed and args.packed_eos_id is None:
        p.error("--packed requires --packed-eos-id")
    return args


def main():
    args = parse_args()
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
        make_causal_lm_loss_sum,
    )
    from neuronx_distributed_tpu.trainer import (
        TrainingMetrics,
        default_batch_spec,
        fit,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        transformer_flops_per_token,
    )
    from neuronx_distributed_tpu.utils import Timeline, initialize_distributed
    from neuronx_distributed_tpu.utils.common import ensure_virtual_devices
    from neuronx_distributed_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    if args.virtual_devices:
        ensure_virtual_devices(args.virtual_devices)
    cache_dir = configure_compile_cache()
    initialize_distributed()
    dev = jax.devices()[0]
    print(f"devices: {len(jax.devices())} x {dev.device_kind} "
          f"(platform {dev.platform}); compute dtype {args.dtype}; "
          f"compile cache {cache_dir}", flush=True)
    nxd.initialize_model_parallel(
        tensor_parallel_size=args.tp,
        pipeline_parallel_size=args.pp,
        context_parallel_size=args.cp,
        kv_size_multiplier=args.kv_multiplier,
    )

    # one TrainingConfig drives dtypes, mesh, pipeline and optimizer
    config = nxd.training_config(
        tensor_parallel_size=args.tp,
        pipeline_parallel_size=args.pp,
        context_parallel_size=args.cp,
        kv_size_multiplier=args.kv_multiplier,
        num_microbatches=args.microbatches,
        schedule=args.pp_schedule,
        virtual_stages=args.virtual_stages,
        packed_inputs=args.packed and args.pp > 1,
        learning_rate=args.lr,
        lr_schedule="cosine",
        warmup_steps=args.warmup_steps,
        total_steps=max(args.steps, args.warmup_steps + 1),
        zero_one_enabled=not args.no_zero1,
        compute_dtype=args.dtype,
        param_dtype="float32",
        seed=args.seed,
    )
    cfg = getattr(LlamaConfig, args.preset)(
        max_seq_len=args.seq_len,
        sequence_parallel=not args.no_sp,
        attention_impl=args.attention,
        remat=args.remat,
        scan_layers=args.scan_layers,
        dtype=config.jnp_compute_dtype,
        param_dtype=config.jnp_param_dtype,
    )

    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg), (jnp.zeros((1, args.seq_len), jnp.int32),),
        seed=args.seed,
    )
    # warmup-cosine comes from the config contract (OptimizerConfig.lr_schedule)
    opt = initialize_parallel_optimizer(config, model)
    bspec = {"ids": default_batch_spec(), "labels": default_batch_spec()}
    if args.packed:
        bspec.update({"positions": default_batch_spec(),
                      "segment_ids": default_batch_spec()})
    # token-exact (loss_sum, tok) loss; --loss-chunk > 0 additionally chunks
    # the lm-head+CE so [B,S,V] logits never materialize (TPU HBM saver)
    loss_fn = make_causal_lm_loss_sum(chunk_size=args.loss_chunk)

    # data: NXDT corpus through the native loader, or synthetic
    dp = nxd.get_data_parallel_size()
    if args.data and args.packed:
        import numpy as np

        from neuronx_distributed_tpu.data import TokenDataset
        from neuronx_distributed_tpu.data.loader import read_token_file
        from neuronx_distributed_tpu.data.packing import pack_documents, segment_positions

        TokenDataset(args.data).validate_vocab(cfg.vocab_size)
        toks = np.asarray(read_token_file(args.data))
        cuts = np.where(toks == args.packed_eos_id)[0]
        docs = [d[d != args.packed_eos_id] for d in np.split(toks, cuts + 1)]
        docs = [d for d in docs if d.size]
        ids_all, labels_all, segs_all = pack_documents(
            docs, seq_len=args.seq_len, eos_id=args.packed_eos_id)
        pos_all = segment_positions(segs_all)
        n_rows = ids_all.shape[0]
        if n_rows < args.batch_size:
            raise SystemExit(
                f"packing produced {n_rows} rows < batch size {args.batch_size}")
        print(f"packed {len(docs)} documents into {n_rows} rows of {args.seq_len}")

        perm_cache = {}

        def epoch_perm(e):
            if e not in perm_cache:
                perm_cache.clear() if len(perm_cache) > 2 else None
                perm_cache[e] = np.random.RandomState(args.seed + int(e)).permutation(n_rows)
            return perm_cache[e]

        def next_batch(step):
            # exact one-pass-per-epoch shuffle: element i of the batch is
            # global sample step*B+i, mapped through its OWN epoch's
            # permutation — no duplicated/skipped rows at epoch boundaries
            B = args.batch_size
            idxs = np.arange(step * B, (step + 1) * B)
            epochs = idxs // n_rows
            sel = np.empty(B, np.int64)
            for e in np.unique(epochs):
                m = epochs == e
                sel[m] = epoch_perm(e)[idxs[m] % n_rows]
            return {"ids": jnp.asarray(ids_all[sel]),
                    "labels": jnp.asarray(labels_all[sel]),
                    "positions": jnp.asarray(pos_all[sel]),
                    "segment_ids": jnp.asarray(segs_all[sel])}
    elif args.data:
        from neuronx_distributed_tpu.data import TokenDataLoader, TokenDataset

        ds = TokenDataset(args.data)
        ds.validate_vocab(cfg.vocab_size)
        loader = TokenDataLoader(
            ds, batch_size=args.batch_size, seq_len=args.seq_len,
            dp_rank=0, dp_size=1, seed=args.seed)  # single-controller: full batch
        L = max(len(loader), 1)
        state = {"iter": None, "expected": None}

        def next_batch(step):
            # step-indexed facade over the epoch iterator: any jump (fit()'s
            # resume, an epoch boundary) re-seeks by epoch + skip so the
            # shuffle order matches an uninterrupted run
            if state["expected"] != step:
                loader.set_epoch(step // L, skip_batches=step % L)
                state["iter"] = iter(loader)
            b = next(state["iter"], None)
            if b is None:
                loader.set_epoch(step // L)
                state["iter"] = iter(loader)
                b = next(state["iter"])
            state["expected"] = step + 1
            return {"ids": jnp.asarray(b["ids"]), "labels": jnp.asarray(b["labels"])}
    else:
        def next_batch(step):
            k = jax.random.fold_in(jax.random.PRNGKey(args.seed), step)
            ids = jax.random.randint(k, (args.batch_size, args.seq_len), 0, cfg.vocab_size)
            return {"ids": ids, "labels": jnp.roll(ids, -1, axis=1)}

    flops_tok = transformer_flops_per_token(
        cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
        args.seq_len, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_)
    metrics = TrainingMetrics(args.metrics_file) if args.metrics_file else None
    # MFU against the device's published peak, from the one table — which
    # raises on a TPU kind it does not hold.  A CPU run reports no MFU.
    peak_flops = None
    if dev.platform == "tpu":
        from neuronx_distributed_tpu.utils.profiling import device_spec

        peak_flops = device_spec(dev).peak_flops

    # the whole loop — step/eval/checkpoint/resume/logging — is fit()'s job
    res = fit(
        config, model, opt, next_batch,
        steps=args.steps,
        loss_fn=loss_fn,
        batch_spec=bspec,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        keep_ckpts=args.keep_ckpts,
        ckpt_save_dtype=jnp.bfloat16 if args.ckpt_bf16 else None,
        checkpoint_on_signal=args.ckpt_on_signal,
        resume=args.resume,
        scalar_dir=args.scalar_dir,
        metrics=metrics,
        timeline=Timeline(args.timeline) if args.timeline else None,
        flops_per_token=flops_tok,
        peak_flops=peak_flops,
        log_every=10,
    )
    print(f"done: final loss {res.final_loss:.4f}")


if __name__ == "__main__":
    main()
