#!/usr/bin/env python
"""sala_step0.py — one lightning layer's core and one block-sparse layer's
selection + attention ALONE at a cell's geometry, on the chip, before (and
beside) the engine: the table of ``PERF.md`` §6 (PR 29, step 0).

    python benchmarks/tools/sala_step0.py --workload minicpm-sala.serve-longdocs

Lightning core (``ops.lightning_attention``): the token-by-token scan
against the chunked form at several block widths, for a decode step of all
slots and for one prefill chunk.  Sparse layer (``ops.block_select``):
selection + attention over the chosen pages against the same walk over
EVERY visible page (the dense rule forced), for a decode step of all slots
and one chunk, at two context lengths; and the three ways to take the top-k
(``lax.top_k``, a full sort, a threshold at the k-th value).  Each variant
runs ``--reps`` times inside ONE program (a ``lax.scan`` whose carry feeds
the next repetition, so nothing overlaps and no dispatch is timed); the
number printed is microseconds a repetition.  Results also go to
``chiprun_out/sala_step0.json``.
"""

import argparse
import dataclasses
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
    ap.add_argument("--contexts", default="8192,20000")
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny sizes, on any platform: "
                         "a control-flow check, no number means anything")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import common, manifest
    from neuronx_distributed_tpu.models.hybrid import (
        lightning_dims,
        sparse_spec,
    )
    from neuronx_distributed_tpu.ops import block_select as bsel
    from neuronx_distributed_tpu.ops import lightning_attention as la

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    s = cell.config["serving"]
    _, mcfg = common.program_config(cell.config["program"])
    B, W, page, T = (s["slots"], s["prefill_chunk_tokens"], s["page_size"],
                     s["max_total_len"])
    NH, D = lightning_dims(mcfg)
    NQ, NKV = mcfg.num_heads, mcfg.num_kv_heads
    reps, results = args.reps, {}
    key = jax.random.PRNGKey(0)
    print(f"[step0] device {jax.devices()[0].device_kind}; {B} slots, chunk "
          f"{W}, page {page}; lightning {NH} x {D}; softmax {NQ} q / {NKV} kv")

    def note(name, us):
        results[name] = us
        print(f"[step0] {name}: {us:.1f} us", flush=True)

    # ---- lightning core --------------------------------------------------
    def lightning_case(rows_b, rows_s, label):
        q, k, v = (jax.random.normal(kk, (reps, rows_b, rows_s, NH, D),
                                     jnp.bfloat16)
                   for kk in jax.random.split(key, 3))
        st0 = jnp.zeros((rows_b, NH, D, D), jnp.float32)

        def variant(step):
            @jax.jit
            def run(st, q, k, v):
                def body(st, x):
                    o, st = step(*x, st)
                    return st, jnp.sum(o.astype(jnp.float32))
                return jax.lax.scan(body, st, (q, k, v))
            return run

        note(f"lightning {label} token scan", timed(variant(
            lambda q, k, v, st: la.lightning_scan_reference(
                q, k, v, None, st)), st0, q, k, v, reps=reps))
        for c in ((64, 128, 256) if rows_s > 1 else (1,)):
            note(f"lightning {label} chunked XLA block {c}", timed(variant(
                lambda q, k, v, st, c=c: la.lightning_attention(
                    q, k, v, None, st, chunk_rows=c)), st0, q, k, v,
                reps=reps))

    lightning_case(B, 1, f"decode {B} slots")
    lightning_case(1, W, f"chunk {W} rows")

    # ---- sparse layer ----------------------------------------------------
    spec = sparse_spec(mcfg)
    dense = dataclasses.replace(spec, dense_len=2 ** 30)
    PP = T // page
    NP = B * PP + 1
    kk = jax.random.split(key, 8)
    ck = jax.random.normal(kk[0], (NP, NKV, page, D), jnp.bfloat16)
    cv = jax.random.normal(kk[1], (NP, NKV, page, D), jnp.bfloat16)
    kc = jax.random.normal(kk[2], (NP, page // spec.kernel_stride, NKV, D),
                           jnp.bfloat16)
    tables = (1 + np.arange(B * PP).reshape(B, PP)).astype(np.int32)

    def sparse_case(ctx, rows_b, rows_s, sp, label):
        q = jax.random.normal(kk[3], (rows_b, rows_s, NQ, D), jnp.bfloat16)
        k = jax.random.normal(kk[4], (rows_b, rows_s, NKV, D), jnp.bfloat16)
        v = jax.random.normal(kk[5], (rows_b, rows_s, NKV, D), jnp.bfloat16)
        # a row of `ctx` tokens ending at cell ctx (no left pad): the call's
        # rows are its last rows_s
        valid = np.zeros((rows_b, T), np.int32)
        valid[:, :ctx] = 1
        off = np.full((rows_b,), ctx - rows_s, np.int32)

        @jax.jit
        def run(q, cache):
            def body(carry, _):
                qq, cache = carry
                out, cache, _ = bsel.sparse_paged_attention(
                    qq, k, v, cache, jnp.asarray(tables[:rows_b]),
                    jnp.asarray(off), jnp.asarray(valid), sp, True)
                return (q + 0 * out.astype(q.dtype), cache), None
            return jax.lax.scan(body, (q, cache), None, length=reps)[0][0]

        note(f"sparse {label} ctx {ctx}", timed(run, q, (ck, cv, kc),
                                                reps=reps))

    for ctx in (int(c) for c in args.contexts.split(",")):
        sparse_case(ctx, B, 1, spec, f"decode {B} slots select + chosen walk")
        sparse_case(ctx, B, 1, dense, f"decode {B} slots dense walk (+ scores)")
        sparse_case(ctx, 1, W, spec, f"chunk {W} rows select + masked walk")
        sparse_case(ctx, 1, W, dense, f"chunk {W} rows dense walk (+ scores)")

    # ---- the top-k alone ---------------------------------------------------
    for rows, label in ((B, f"decode {B} rows"), (W, f"chunk {W} rows")):
        sc = jax.random.uniform(kk[6], (reps, NKV, rows, PP), jnp.float32)

        def topk(x):
            _, idx = jax.lax.top_k(x, spec.topk)
            return jnp.any(idx[..., None] == jnp.arange(PP), axis=-2)

        def by_sort(x):
            order = jnp.argsort(-x, axis=-1)
            return jnp.argsort(order, axis=-1) < spec.topk

        def threshold(x):
            kth = jnp.sort(x, axis=-1)[..., PP - spec.topk, None]
            return x >= kth

        for name, f in (("lax.top_k", topk), ("two argsorts", by_sort),
                        ("threshold at the k-th value", threshold)):
            run = jax.jit(lambda x, f=f: jax.lax.map(
                lambda y: jnp.sum(f(y)), x))
            note(f"top-{spec.topk} of {PP}, {label}, {name}",
                 timed(run, sc, reps=reps))

    out = os.path.join(manifest.REPO_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "sala_step0.json"), "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
