"""Real-scale convergence evidence (VERDICT r4 next-step #5).

Machine-checked convergence in the mold of the reference's cross-platform
golden comparison (``test/integration/combinatorial_tests/common/
compare_gpu_trn1_metrics.py:19-60``, which EMA-smooths two hardware runs of
the SAME config and requires <=1% pointwise deviation after warmup):

- ``golden`` (CPU): run the fixed PARITY config (a small-but-real Llama on
  deterministic Markov-chain data) and write the loss curve to
  ``docs/convergence/golden_parity/`` — the committed golden trajectory.
- ``parity`` (TPU): run the IDENTICAL config on the chip and machine-compare
  against the committed golden with ``testing.convergence`` (1% smoothed
  tolerance — the reference's own bar for cross-platform parity).
- ``scale`` (TPU): run the ~400M bench-class model for a few hundred steps
  single-chip; the machine check is smoothed-curve improvement (a CPU golden
  at this scale is computationally dishonest — hours per run — so the curve
  itself is committed as the golden for future silicon rounds).

Each mode prints ONE JSON line; ``parity`` and ``scale`` need the chip.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_DIR = os.path.join(REPO, "docs", "convergence", "golden_parity")
SCALE_DIR = os.path.join(REPO, "docs", "convergence", "scale_438m")

BRANCHING = 16  # Markov fan-out: optimal loss floor = log(16) ~= 2.77 nats


def markov_batch(rng: np.random.RandomState, B: int, S: int, vocab: int):
    """Deterministic learnable LM data: a fixed random successor table
    (seed 0) defines a Markov chain; batches walk it.  Identical host-side
    generation on every platform, so CPU and TPU runs see the same bytes."""
    succ = np.random.RandomState(0).randint(0, vocab, (vocab, BRANCHING))
    out = np.empty((B, S + 1), np.int64)
    state = rng.randint(0, vocab, B)
    out[:, 0] = state
    for t in range(1, S + 1):
        state = succ[state, rng.randint(0, BRANCHING, B)]
        out[:, t] = state
    return out[:, :-1].astype(np.int32), out[:, 1:].astype(np.int32)


def run(mode: str, steps: int, out_dir: str, force_cpu: bool) -> dict:
    if force_cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
        causal_lm_loss,
    )
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
    )
    from neuronx_distributed_tpu.trainer.scalar_log import ScalarWriter

    platform = jax.devices()[0].platform
    if mode == "scale":
        if platform == "cpu":
            raise RuntimeError("scale mode is a TPU job (hours on CPU)")
        # bench-class ~400M model; vocab shrunk to the Markov task's range
        cfg = LlamaConfig(
            vocab_size=4096, hidden_size=1536, intermediate_size=4096,
            num_layers=12, num_heads=12, num_kv_heads=12, head_dim=128,
            max_seq_len=2048, sequence_parallel=False, remat="selective",
            attention_impl="flash",
        )
        B, S, lr = 4, 2048, 3e-4
    else:  # the parity config — MUST stay identical between golden/parity
        cfg = LlamaConfig(
            vocab_size=512, hidden_size=256, intermediate_size=688,
            num_layers=4, num_heads=4, num_kv_heads=4, max_seq_len=256,
            sequence_parallel=False, remat="none", attention_impl="dense",
            dtype=jnp.float32, param_dtype=jnp.float32,
        )
        B, S, lr = 8, 256, 2e-3

    nxd.destroy_model_parallel()
    nxd.initialize_model_parallel(tensor_parallel_size=1,
                                  devices=jax.devices()[:1])
    config = nxd.training_config(
        learning_rate=lr,
        compute_dtype="float32" if mode != "scale" else "bfloat16",
    )
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg), (jnp.zeros((1, S), jnp.int32),))
    opt = initialize_parallel_optimizer(config, model)
    step_fn = make_train_step(
        config, model, opt, causal_lm_loss,
        batch_spec={"ids": default_batch_spec(), "labels": default_batch_spec()})

    # stage into a sibling dir and swap in only on success: an interrupted
    # run must never destroy or truncate the existing (committed) curve
    stage_dir = out_dir.rstrip("/") + ".tmp"
    if os.path.isdir(stage_dir):
        import shutil

        shutil.rmtree(stage_dir)
    os.makedirs(stage_dir)
    writer = ScalarWriter(stage_dir)
    data_rng = np.random.RandomState(1234)  # one stream -> step-deterministic
    params, state = model.params, opt.state
    losses = []
    for step in range(steps):
        ids, labels = markov_batch(data_rng, B, S, cfg.vocab_size)
        params, state, m = step_fn(
            params, state,
            {"ids": jnp.asarray(ids), "labels": jnp.asarray(labels)},
            jax.random.PRNGKey(step))
        loss = float(m["loss"])
        losses.append(loss)
        writer.scalars(step, loss=loss)
        if step % 10 == 0:
            print(f"# step {step} loss {loss:.4f}", file=sys.stderr, flush=True)
    writer.close()
    os.makedirs(out_dir, exist_ok=True)
    for f in os.listdir(out_dir):
        if f == "scalars.jsonl" or f.startswith("events.out.tfevents"):
            os.remove(os.path.join(out_dir, f))
    for f in os.listdir(stage_dir):
        os.replace(os.path.join(stage_dir, f), os.path.join(out_dir, f))
    os.rmdir(stage_dir)
    return {"platform": platform, "steps": steps, "losses": losses,
            "final_loss": losses[-1], "out_dir": out_dir}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("mode", choices=["golden", "parity", "scale"])
    p.add_argument("--steps", type=int, default=0, help="0 = mode default")
    p.add_argument("--out", default=None)
    p.add_argument("--tolerance-pct", type=float, default=1.0)
    p.add_argument("--warmup", type=int, default=20)
    args = p.parse_args()

    steps = args.steps or (200 if args.mode == "scale" else 160)
    if args.mode == "golden":
        out = args.out or GOLDEN_DIR
        r = run("golden", steps, out, force_cpu=True)
        print(json.dumps({"kind": "convergence_golden", "ok": True,
                          "platform": r["platform"], "steps": steps,
                          "final_loss": round(r["final_loss"], 4)}))
        return 0

    from neuronx_distributed_tpu.testing.convergence import (
        compare_scalar_logs,
        smoothed,
    )

    if args.mode == "parity":
        # fail in milliseconds, not after burning the TPU window on a run
        # that cannot be compared: the golden must exist AND hold enough
        # post-warmup records
        golden_file = os.path.join(GOLDEN_DIR, "scalars.jsonl")
        n_golden = 0
        if os.path.isfile(golden_file):
            from neuronx_distributed_tpu.trainer.scalar_log import read_scalars

            n_golden = len(read_scalars(GOLDEN_DIR, "loss"))
        if n_golden <= args.warmup + 1:
            print(json.dumps({"kind": "convergence_parity", "ok": False,
                              "error": f"golden missing or truncated "
                              f"({n_golden} records <= warmup {args.warmup}) "
                              f"at {golden_file} — regenerate with "
                              "`convergence_run.py golden`"}))
            return 1
        out = args.out or os.path.join(REPO, "docs", "convergence", "tpu_parity")
        r = run("parity", steps, out, force_cpu=False)
        verdict = compare_scalar_logs(
            out, GOLDEN_DIR, tag="loss", warmup_steps=min(args.warmup, steps - 1),
            tolerance_pct=args.tolerance_pct)
        print(json.dumps({
            "kind": "convergence_parity", "ok": bool(verdict),
            "platform": r["platform"], "steps": steps,
            "max_deviation_pct": round(verdict.max_deviation_pct, 3),
            "worst_step": verdict.worst_step,
            "final_loss": round(r["final_loss"], 4)}))
        return 0 if verdict else 1

    out = args.out or SCALE_DIR
    r = run("scale", steps, out, force_cpu=False)
    sm = smoothed(r["losses"])
    w = min(args.warmup, len(sm) - 1)
    improved = sm[-1] < 0.8 * sm[w]
    finite = all(np.isfinite(r["losses"]))
    print(json.dumps({
        "kind": "convergence_scale", "ok": bool(improved and finite),
        "platform": r["platform"], "steps": steps,
        "smoothed_start": round(sm[w], 4), "smoothed_final": round(sm[-1], 4),
        "final_loss": round(r["final_loss"], 4)}))
    return 0 if (improved and finite) else 1


if __name__ == "__main__":
    sys.exit(main())
