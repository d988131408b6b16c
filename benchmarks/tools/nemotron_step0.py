#!/usr/bin/env python
"""nemotron_step0.py — one Mamba-2 layer's core ALONE at a cell's geometry,
on the chip, before (and beside) the engine: the table of ``PERF.md`` §6
(PR 32, step 0).

    python benchmarks/tools/nemotron_step0.py --workload nemotron-3-nano.serve-agents

The scan (``ops.ssm_scan``): the token-by-token scan against the chunked
form at several block widths for one prefill chunk; the one-token step for
a decode of all slots, on the state alone and as the mixer runs it — state
rows gathered from the ``[slots, heads, P, N]`` float32 array at traced row
ids, stepped, scattered back into the donated array — against its byte
bound (every live row read and written once); the convolution with its
carried taps, both shapes.  Each variant runs ``--reps`` times inside ONE
program (a ``lax.scan`` whose carry feeds the next repetition, so nothing
overlaps and no dispatch is timed); the number printed is microseconds a
repetition.  Results also go to ``chiprun_out/nemotron_step0.json``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def timed(fn, *args, reps):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / reps * 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--reps", type=int, default=16)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny sizes, on any platform: "
                         "a control-flow check, no number means anything")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import common, manifest
    from neuronx_distributed_tpu.models.hybrid import ssm_dims
    from neuronx_distributed_tpu.ops import ssm_scan as ssm

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    s = cell.config["serving"]
    _, mcfg = common.program_config(cell.config["program"])
    slots, W = s["slots"], s["prefill_chunk_tokens"]
    NH, P, G, N, K = ssm_dims(mcfg)
    conv_ch = NH * P + 2 * G * N
    act = mcfg.dtype
    reps, results = args.reps, {}
    key = jax.random.PRNGKey(0)
    peak = None if args.rehearse else manifest.peaks_for(
        str(jax.devices()[0].device_kind))
    print(f"[step0] device {jax.devices()[0].device_kind}; {slots} slots, "
          f"chunk {W}; mamba2 {NH} heads x {P}, {G} groups, state {N}, "
          f"conv {K}")

    def note(name, us):
        results[name] = us
        print(f"[step0] {name}: {us:.1f} us", flush=True)

    A = -jnp.exp(jnp.log(jax.random.uniform(key, (NH,), jnp.float32, 1., 16.)))
    D = jnp.ones((NH,), jnp.float32)

    def inputs(rows_b, rows_s):
        kk = jax.random.split(key, 4)
        x = jax.random.normal(kk[0], (reps, rows_b, rows_s, NH, P), act)
        Bm = jax.random.normal(kk[1], (reps, rows_b, rows_s, G, N), act)
        Cm = jax.random.normal(kk[2], (reps, rows_b, rows_s, G, N), act)
        dt = jax.nn.softplus(jax.random.normal(
            kk[3], (reps, rows_b, rows_s, NH), jnp.float32) - 4.0)
        return x, Bm, Cm, dt

    def variant(step):
        @jax.jit
        def run(st, *xs):
            def body(st, x):
                y, st = step(*x, st)
                return st, jnp.sum(y.astype(jnp.float32))
            return jax.lax.scan(body, st, xs)
        return run

    # ---- the scan over a prefill chunk ----------------------------------
    xs = inputs(1, W)
    st0 = jnp.zeros((1, NH, P, N), jnp.float32)
    note(f"scan chunk {W} rows token scan", timed(variant(
        lambda x, b, c, dt, st: ssm.ssm_scan_reference(
            x, b, c, dt, A, D, None, st)), st0, *xs, reps=reps))
    for c in (64, 128, 256):
        note(f"scan chunk {W} rows chunked XLA block {c}", timed(variant(
            lambda x, b, c_, dt, st, c=c: ssm.ssm_scan(
                x, b, c_, dt, A, D, None, st, chunk_rows=c)), st0, *xs,
            reps=reps))

    # ---- the decode step --------------------------------------------------
    xs = inputs(slots, 1)
    st0 = jnp.zeros((slots, NH, P, N), jnp.float32)
    note(f"step {slots} slots on the state alone", timed(variant(
        lambda x, b, c, dt, st: ssm.ssm_scan(x, b, c, dt, A, D, None, st)),
        st0, *xs, reps=reps))
    rows = jnp.asarray(np.random.RandomState(0).permutation(slots), jnp.int32)

    def gather_step(x, b, c, dt, states):
        y, st = ssm.ssm_scan(x, b, c, dt, A, D, None, states[rows])
        return y, states.at[rows].set(st)

    run = variant(gather_step)
    run = jax.jit(run, donate_argnums=(0,))
    us = timed(lambda *xs_: run(jnp.zeros_like(st0), *xs_), *xs, reps=reps)
    note(f"step {slots} slots gather rows - step - scatter (donated)", us)
    state_bytes = 2 * slots * NH * P * N * 4
    if peak is not None:
        bound = state_bytes / peak["hbm_bytes_per_s"] * 1e6
        note(f"step {slots} slots byte bound (state read + written once)",
             bound)
        print(f"[step0] the step runs at {100 * bound / us:.1f}% of its "
              "byte bound")

    # ---- the convolution --------------------------------------------------
    w = jax.random.normal(key, (K, conv_ch), act)
    bias = jnp.zeros((conv_ch,), act)
    for rows_b, rows_s, label in ((1, W, f"chunk {W} rows"),
                                  (slots, 1, f"step {slots} slots")):
        x = jax.random.normal(key, (reps, rows_b, rows_s, conv_ch), act)
        valid = jnp.ones((rows_b, rows_s), jnp.int32)

        @jax.jit
        def conv(taps, x):
            def body(taps, xx):
                y, taps = ssm.causal_conv(xx, taps, w, bias, valid)
                return taps, jnp.sum(y.astype(jnp.float32))
            return jax.lax.scan(body, taps, x)

        note(f"conv {label}", timed(
            conv, jnp.zeros((rows_b, K - 1, conv_ch), act), x, reps=reps))

    out = os.path.join(manifest.REPO_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "nemotron_step0.json"), "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
