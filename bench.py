"""Benchmark: Llama pretrain throughput on the attached TPU chip(s).

Prints ONE JSON metric line on success and exits 0.  When no TPU rung ran —
no accelerator, every rung failed, a rung hung — it prints NO metric line
and exits non-zero: a missing chip is a failure, never a CPU number.

Structure:

- the parent process never imports jax and runs ONE child at a time.  The
  chip belongs to one process: a parent that had touched jax would hold it,
  and the measuring child would fail or hang; two children at once would
  fight over it the same way;
- a ladder of configs is tried in order (flash attention + big batch first,
  then dense, then smaller batches — the later rungs exist for what the
  earlier ones cannot fit) and the first success wins.  A rung that times
  out ends the run: the chip is wedged, and the next rung would only hang
  behind it;
- every successful measurement times TWO rungs over the same compiled
  program: prefetch OFF (host batch + per-step metric sync — the naive hot
  path) and prefetch ON (DevicePrefetcher staging + pipelined one-step-late
  fetch — the fit(prefetch=2, defer_metrics) production path).  The ON rung
  is the headline ``value``;
- every result names the ``platform``, ``device_kind`` and ``device_count``
  it ran on, and MFU is computed against the one peak table
  (``obs.perf.DEVICE_SPECS``) — an unknown device kind is an error;
- ``--platform=cpu`` is an explicit REHEARSAL of the harness on the CPU at a
  tiny size: its line is labelled ``cpu``, carries another metric name, and
  no MFU or roofline figure.

The reference publishes no absolute numbers (BASELINE.md), so ``vs_baseline``
is measured against the north-star target of 35% MFU (BASELINE.json): 1.0
means exactly 35% MFU on this chip; >1 beats the target.

Model: Llama-shaped decoder sized to fit a single v5e chip's 16 GB HBM for
full training (fp32 master params + fp32 Adam states + bf16 compute), seq
2048 — the single-chip slice of the Llama-2-7B TP=8 pretrain config
(reference tp_zero1_llama2_7b_hf_pretrain.sh:19-36).
"""

import argparse
import json
import math
import os
import subprocess
import sys
import time

# (attention_impl, batch, remat, loss) tried in order on the TPU; first
# success wins.  flash-without-remat leads: flash attention never
# materializes the [S,S] score matrix, so the 438M bench model's activations
# fit HBM un-remated and the recompute FLOPs remat would add (not counted by
# the MFU formula's 6*params accounting) are simply not spent.  A batch-16
# rung tops the ladder (selective remat to be HBM-safe).  loss="chunked:N"
# computes the lm-head + CE per N-token chunk under remat — the [B,S,V]
# logits (the step's biggest activation, ~1 GB bf16 at b16/s2048/v32k, plus
# fp32 softmax residuals) never reach HBM, freeing the memory that gates the
# big-batch rungs.
LADDER = [
    ("flash", 16, "none", "chunked:512"),
    ("flash", 16, "selective", "chunked:512"),
    ("flash", 16, "selective", "mean"),
    ("flash", 8, "none", "chunked:512"),
    ("flash", 8, "none", "mean"),
    ("flash", 8, "selective", "mean"),
    ("flash", 4, "selective", "mean"),
    ("dense", 4, "selective", "mean"),
    ("dense", 2, "selective", "mean"),
]
# a cold compile of the big train-step programs is minutes, not seconds;
# with the persistent cache warm an attempt needs seconds
ATTEMPT_TIMEOUT_S = 2400


def run_measurement(platform: str, attn: str, batch: int, remat: str,
                    loss: str = "mean",
                    profile_out: "str | None" = None) -> dict:
    """Child-process body: build the model, time steps, return the result.

    Raises on any failure; the parent ladder decides what to try next."""
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
        transformer_flops_per_token,
        mfu,
    )

    devices = jax.devices()
    n = len(devices)
    if devices[0].platform != platform:
        # never report a number from another platform than the one asked for
        raise RuntimeError(
            f"requested {platform} but jax.devices() -> {devices[0].platform}")
    on_tpu = platform == "tpu"

    if on_tpu:
        # ~400M-param Llama slice: 7B's hidden layout /4, seq 2048
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_layers=12, num_heads=12, num_kv_heads=12, head_dim=128,
            max_seq_len=2048, sequence_parallel=n > 1, remat=remat,
            attention_impl=attn,
        )
        seq, steps, warmup = 2048, 10, 3
    else:  # CPU rehearsal of the harness
        cfg = LlamaConfig.tiny(sequence_parallel=False, remat="none")
        batch, seq, steps, warmup = 2, 64, 3, 1

    tp = n if n > 1 else 1
    nxd.initialize_model_parallel(tensor_parallel_size=tp, devices=devices)
    config = nxd.training_config(tensor_parallel_size=tp, learning_rate=1e-4)

    if loss.startswith("chunked"):
        from neuronx_distributed_tpu.models import make_causal_lm_loss_sum

        chunk = int(loss.split(":", 1)[1]) if ":" in loss else 512
        loss_fn = make_causal_lm_loss_sum(chunk_size=chunk)
    else:
        loss_fn = causal_lm_loss

    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg), (jnp.zeros((1, seq), jnp.int32),)
    )
    opt = initialize_parallel_optimizer(config, model)
    step = make_train_step(
        config, model, opt, loss_fn,
        batch_spec={"ids": default_batch_spec(), "labels": default_batch_spec()},
    )

    import numpy as np

    from neuronx_distributed_tpu.data.prefetch import DevicePrefetcher
    from neuronx_distributed_tpu.trainer.trainer import _batch_shardings

    np_ids = np.asarray(
        jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0,
                           cfg.vocab_size))
    # HOST batches for both passes: the host→device staging cost must be in
    # the measurement (it is exactly what the prefetch rung overlaps away)
    host_batch = {"ids": np_ids, "labels": np.roll(np_ids, -1, axis=1)}
    stage_shardings = _batch_shardings(
        model.mesh, {"ids": default_batch_spec(), "labels": default_batch_spec()})
    params, state = model.params, opt.state

    if on_tpu and attn == "flash":
        # a flash rung measures the Mosaic kernel or nothing: an interpreted
        # or substituted attention must not publish under its name
        text = step.lower(params, state, host_batch,
                          jax.random.PRNGKey(0)).compile().as_text()
        if "tpu_custom_call" not in text:
            raise RuntimeError(
                "flash rung compiled without a Mosaic kernel "
                "(no tpu_custom_call in the train step)")

    # Synchronization discipline: the sync is ``device_get`` of the final
    # step's loss — the bytes cannot exist before the step executed, and
    # step i+1 consumes step i's params, so fetching the LAST loss
    # transitively proves every timed step ran.  Anything that still slips
    # through dies on the plausibility gate below.  The fetched value is
    # also checked finite: a step that executed but produced NaN is a failed
    # attempt, not a throughput number.
    # Compile accounting (obs.compile_ledger): jit compiles synchronously
    # before dispatch returns, so the FIRST warmup step's dispatch wall IS
    # the cold compile cost (with the persistent cache warm it measures the
    # cache replay), and a later dispatch of the same program is the warm
    # cost.
    from neuronx_distributed_tpu.obs.compile_ledger import CompileLedger

    ledger = CompileLedger()
    for i in range(warmup):
        t_disp = time.perf_counter()
        params, state, m = step(params, state, host_batch, jax.random.PRNGKey(i))
        ledger.record_compile(
            "train_step", "cold" if i == 0 else "warm",
            (time.perf_counter() - t_disp) * 1e3, kind="jit")
    if warmup < 2:
        # the CPU rehearsal warms once; one extra dispatch gives the warm number
        t_disp = time.perf_counter()
        params, state, m = step(params, state, host_batch, jax.random.PRNGKey(0))
        ledger.record_compile("train_step", "warm",
                              (time.perf_counter() - t_disp) * 1e3, kind="jit")
    ledger.declare_warmup_done("bench")
    compile_walls = [r["wall_ms"] for r in ledger.rows
                     if r["event"] == "compile"]
    compile_cold_ms, compile_warm_ms = compile_walls[0], compile_walls[-1]
    float(jax.device_get(m["loss"]))

    # Prefetch-OFF rung: the naive hot path — a host batch handed to the
    # jitted step (implicit h2d) and a blocking per-step metric fetch.
    # host_blocked_frac_sync is the fraction of wall time the host spent
    # inside those fetches (≈ the device time the host serialized behind).
    t0 = time.perf_counter()
    blocked_s = 0.0
    for i in range(steps):
        params, state, m = step(params, state, host_batch, jax.random.PRNGKey(i))
        tb = time.perf_counter()
        loss_val = float(jax.device_get(m["loss"]))
        blocked_s += time.perf_counter() - tb
    dt_sync = time.perf_counter() - t0
    if not math.isfinite(loss_val):
        raise RuntimeError(f"non-finite loss after {warmup + steps} steps: {loss_val}")
    tokens_per_sec_sync = batch * seq * steps / dt_sync
    host_blocked_frac_sync = blocked_s / max(dt_sync, 1e-9)

    # Prefetch-ON rung (the async hot path, and the headline number):
    # batches staged onto the device ahead of the step by a background
    # thread, metric fetch pipelined one step behind the dispatch — the
    # same overlap fit(prefetch=N, defer_metrics=True) runs in production.
    # staged (sharding-committed) inputs are a DIFFERENT jit cache key than
    # the host batches above — one untimed warm step keeps the retrace out
    # of the timed window
    params, state, m = step(params, state,
                            jax.device_put(host_batch, stage_shardings),
                            jax.random.PRNGKey(0))
    float(jax.device_get(m["loss"]))
    prefetcher = DevicePrefetcher(lambda s: host_batch, depth=2,
                                  shardings=stage_shardings)
    # --profile-out: capture an XLA device profile of exactly the headline
    # (prefetch-ON) rung — the window whose number gets published
    from contextlib import nullcontext

    from neuronx_distributed_tpu.obs.tracing import device_trace

    prof = device_trace(profile_out) if profile_out else nullcontext()
    try:
        with prof:
            t0 = time.perf_counter()
            blocked_s = 0.0
            m_prev = None
            for i in range(steps):
                staged = prefetcher.get(i)
                params, state, m = step(params, state, staged,
                                        jax.random.PRNGKey(i))
                if m_prev is not None:  # pipelined: read i-1 behind i
                    tb = time.perf_counter()
                    float(jax.device_get(m_prev["loss"]))
                    blocked_s += time.perf_counter() - tb
                m_prev = m
            tb = time.perf_counter()
            loss_val = float(jax.device_get(m["loss"]))
            blocked_s += time.perf_counter() - tb
            dt = time.perf_counter() - t0
    finally:
        prefetcher.close()
    if not math.isfinite(loss_val):
        raise RuntimeError(
            f"non-finite loss after the prefetch pass: {loss_val}")
    host_blocked_frac = blocked_s / max(dt, 1e-9)

    tokens_per_sec_per_chip = batch * seq * steps / dt / n
    where = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": n,
    }
    shape = (f"attn={attn}, batch={batch}, remat={remat}, loss={loss}, "
             f"prefetch=2, model={model.num_parameters()/1e6:.0f}M, "
             f"seq={seq}, device={devices[0].device_kind}")
    overlap = {
        # the overlap story: host-blocked wall-time fraction with the async
        # hot path on (prefetch + pipelined metric fetch) vs the naive
        # per-step-sync loop on the same program
        "host_blocked_frac": round(host_blocked_frac, 4),
        "host_blocked_frac_sync": round(host_blocked_frac_sync, 4),
        "tokens_per_sec_per_chip_sync": round(tokens_per_sec_sync / n, 2),
        # cold = first dispatch of the train-step program (trace + XLA
        # compile, or the persistent-cache replay when warm), warm = a
        # later dispatch of the same compiled program
        "compile_cold_ms": round(compile_cold_ms, 1),
        "compile_warm_ms": round(compile_warm_ms, 1),
    }
    if not on_tpu:
        # harness rehearsal: a CPU rate is not a device metric — another
        # metric name, no MFU, no roofline, no comparison with the target
        return {
            "metric": "cpu_rehearsal_tokens_per_sec",
            "value": round(tokens_per_sec_per_chip, 2),
            "unit": f"tokens/s on cpu — harness rehearsal ({shape})",
            **where, **overlap,
        }

    from neuronx_distributed_tpu.obs.perf import PerfAttribution, device_spec

    spec = device_spec(devices[0])  # unknown device kind: an error, no default
    fpt = transformer_flops_per_token(
        cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
        seq, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
    )
    achieved_mfu = mfu(tokens_per_sec_per_chip, fpt, spec.peak_flops)

    # Roofline attribution of the same rung through the shared perf layer
    # (obs.perf): per-chip model FLOPs joined with the measured wall —
    # mfu_model cross-checks achieved_mfu.
    perf = PerfAttribution(spec=spec)
    perf.note_cost("train_step", fpt * batch * seq / n, 0.0)
    perf.note_phase("train_step", dt * 1e3, calls=float(steps))
    roll = perf.rollup()

    # Physical-plausibility gate: mfu() returns a FRACTION of chip peak; a
    # value >= 1 (tokens/s above peak_flops/flops_per_token) is impossible
    # and means the timing harness did not measure the device.  Hard-fail
    # the attempt so an unsynchronized runtime can never publish a number.
    ceiling = spec.peak_flops / fpt
    if not (0.0 < achieved_mfu < 1.0):
        raise RuntimeError(
            f"implausible measurement: {tokens_per_sec_per_chip:,.0f} tokens/s/chip "
            f"=> mfu={achieved_mfu:.3f} (ceiling {ceiling:,.0f} tokens/s/chip at "
            f"mfu=1.0); the timed loop did not synchronize with device execution"
        )

    return {
        "metric": "llama_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_per_chip, 2),
        "unit": (
            f"tokens/s/chip (mfu={achieved_mfu:.3f}, {shape};"
            f" sync rung: {tokens_per_sec_sync / n:,.0f} tok/s/chip,"
            f" host_blocked {host_blocked_frac_sync:.3f})"
        ),
        "vs_baseline": round(achieved_mfu / 0.35, 3),
        **where, **overlap,
        # roofline attribution (obs.perf) over the headline rung
        "mfu_model": round(roll["mfu"], 4),
        "pct_roofline": round(roll["pct_roofline"], 4),
    }


def child_main(args) -> int:
    from neuronx_distributed_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    try:
        result = run_measurement(args.platform, args.attn, args.batch, args.remat,
                                 args.loss, profile_out=args.profile_out)
    except Exception as e:  # noqa: BLE001 — report, parent decides
        print(f"bench attempt failed: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


def _run_child(extra_args, timeout_s, env=None):
    """One measurement child, waited for to its end (or killed at its time
    limit) before the next may start; ``None`` on timeout."""
    cmd = [sys.executable, os.path.abspath(__file__), "--run", *extra_args]
    try:
        return subprocess.run(
            cmd, env=env or dict(os.environ), capture_output=True, text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return None


def _attempt(platform, attn, batch, remat, loss, profile_out):
    """Returns ``(parsed_json_or_None, timed_out)``."""
    env = dict(os.environ)
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    child_args = [f"--platform={platform}", f"--attn={attn}",
                  f"--batch={batch}", f"--remat={remat}", f"--loss={loss}"]
    if profile_out:
        child_args.append(f"--profile-out={profile_out}")
    proc = _run_child(child_args, ATTEMPT_TIMEOUT_S, env)
    rung = f"{platform}/{attn}/b{batch}/{remat}/{loss}"
    if proc is None:
        print(f"{rung}: timed out after {ATTEMPT_TIMEOUT_S}s", file=sys.stderr)
        return None, True
    if proc.returncode == 0:
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line), False
                except json.JSONDecodeError:
                    continue
    tail = (proc.stderr or "").strip().splitlines()[-12:]
    print(f"{rung} rc={proc.returncode}:\n" + "\n".join(tail), file=sys.stderr)
    return None, False


def parent_main(platform: str = "tpu",
                profile_out: "str | None" = None) -> int:
    """Run the ladder; print the first successful rung's line and return 0,
    or print nothing to stdout and return 1."""
    if platform == "cpu":
        rungs = [("dense", 2, "none", "mean")]  # the explicit rehearsal
    else:
        rungs = LADDER
    for attn, batch, remat, loss in rungs:
        parsed, timed_out = _attempt(platform, attn, batch, remat, loss,
                                     profile_out)
        if parsed is not None:
            print(json.dumps(parsed))
            return 0
        if timed_out:
            break
    print(f"bench: no {platform} rung produced a measurement; "
          "no metric line is printed", file=sys.stderr)
    return 1


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--run", action="store_true", help="internal: run one measurement")
    p.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                   help="tpu (default) measures; cpu is an explicit harness "
                        "rehearsal whose output is labelled cpu")
    p.add_argument("--attn", default="dense")
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--remat", default="selective")
    p.add_argument("--loss", default="mean")
    p.add_argument("--profile-out", default=None,
                   help="directory for an XLA device profile of the "
                        "headline rung (jax.profiler trace)")
    args = p.parse_args()
    sys.exit(child_main(args) if args.run
             else parent_main(args.platform, profile_out=args.profile_out))


if __name__ == "__main__":
    main()
