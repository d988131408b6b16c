#!/usr/bin/env python
"""xing4_step0.py — one latent-attention (MLA) layer's core and one
sublayer's stream mixing ALONE at a cell's geometry, on the chip, before
(and beside) the engine: the table of ``PERF.md`` §6 (PR 36, step 0).

    python benchmarks/tools/xing4_step0.py --workload xing4.0-29b-a4b.serve-longdocs

(a) a decode of every slot at several contexts, ABSORBED over the latent
pages (``ops.latent_attention``), against its byte bound (each visible latent
read once, ``harness/mla_flops.py``) and its operations bound, at several
step widths, and with the stored row unpadded (``rank + rope`` columns, no
multiple of the lanes) where the compiler takes it;
(b) one prefill chunk at the same contexts three ways: absorbed over the
pages; EXPANDED with the up-projection inside the walk a page; expanded by
XLA with the visible prefix's keys and values up-projected once a chunk
(gathered rows, one matmul, dense scores);
(c) one sublayer's mixing maps and stream products (``models.llama.
HyperConnection`` + ``hc_write``) for a chunk's rows and for a decode's.  A
call of it alone is bound by the host's dispatch (PERF.md, PR 36): what the
maps cost is read in the traced cell, ``hc_time_share``.

Each variant is enqueued ``--reps`` times and waited for once; the number
printed is microseconds a call.  A variant the compiler refuses prints why
and goes on.  Results also go to ``chiprun_out/xing4_step0.json``.
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

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / reps * 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--contexts", default="8192,20480,32704")
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny sizes, on any platform: "
                         "a control-flow check, no number means anything")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import common, manifest, mla_flops
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.models.hybrid import mla_softmax_scale
    from neuronx_distributed_tpu.ops import latent_attention as la

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    cfg, s = cell.config, cell.config["serving"]
    _, mcfg = common.program_config(cfg["program"])
    B, W, page, T = (s["slots"], s["prefill_chunk_tokens"], s["page_size"],
                     s["max_total_len"])
    PP, NH, rank = T // page, mcfg.num_heads, mcfg.kv_lora_rank
    dn, dr, dv = mcfg.qk_nope_head_dim, mcfg.qk_rope_head_dim, mcfg.v_head_dim
    R = la.row_dim(rank, dr)
    act, scale = mcfg.dtype, mla_softmax_scale(mcfg)
    contexts = ([T // 4, T // 2] if args.rehearse
                else [int(c) for c in args.contexts.split(",")])
    peak = None if args.rehearse else manifest.peaks_for(
        str(jax.devices()[0].device_kind))
    reps, results = args.reps, {}
    print(f"[step0] device {jax.devices()[0].device_kind}; {B} slots, chunk "
          f"{W}, page {page}; {NH} heads, latent {rank} + rope {dr} stored "
          f"as rows of {R}; nope {dn}, v {dv}")

    def note(name, us, least=None):
        results[name] = us
        print(f"[step0] {name}: {us:.1f} us" + (
            f" (least {least[0] * 1e6:.1f} us, {least[1]} bound: "
            f"{100 * least[0] * 1e6 / us:.1f}%)" if least else ""),
            flush=True)

    def attempt(name, make, least=None):
        try:
            note(name, make(), least)
        except Exception as e:  # noqa: BLE001 — a refused variant is a result
            results[name] = None
            print(f"[step0] {name}: refused: {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:200]}", flush=True)

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    NP = B * PP + 1
    pool = (jax.random.normal(ks[0], (NP, page, R), jnp.float32) * 0.5
            ).astype(act)
    pool = pool.at[:, :, rank + dr:].set(0)
    tables = jnp.asarray(1 + np.arange(B * PP).reshape(B, PP), jnp.int32)
    wk = (jax.random.normal(ks[1], (NH, rank, dn)) * rank ** -0.5).astype(act)
    wv = (jax.random.normal(ks[2], (NH, rank, dv)) * rank ** -0.5).astype(act)

    # (a) the decode, absorbed
    q1 = jax.random.normal(ks[3], (B, 1, NH, rank + dr)).astype(act)
    for ctx in contexts:
        off = jnp.full((B,), ctx - 1, jnp.int32)
        least = peak and mla_flops.decode_least_seconds(B * ctx, cfg, peak)
        for bp in (None, 4, 8):
            attempt(f"decode absorbed ctx {ctx} x {B}"
                    + (f" block_pages {bp}" if bp else ""),
                    lambda bp=bp: timed(
                        lambda q: la.latent_attention(
                            q, pool, tables, off, None, rank=rank,
                            sm_scale=scale, block_pages=bp), q1, reps=reps),
                    least)
    if not args.rehearse:
        # the stored row unpadded: rank + rope columns
        ctx = contexts[1]
        off = jnp.full((B,), ctx - 1, jnp.int32)
        pool576 = pool[:, :, :rank + dr] + 0
        attempt(f"decode absorbed ctx {ctx} x {B}, rows of {rank + dr}",
                lambda: timed(lambda q: la.latent_attention(
                    q, pool576, tables, off, None, rank=rank, sm_scale=scale),
                    q1, reps=reps),
                peak and mla_flops.decode_least_seconds(B * ctx, cfg, peak))
        del pool576

    # (b) a chunk, three ways
    qa = jax.random.normal(ks[4], (1, W, NH, rank + dr)).astype(act)
    qe = jax.random.normal(ks[5], (1, W, NH, dn + dr)).astype(act)
    for ctx in contexts:
        off = jnp.asarray([ctx - W], jnp.int32)
        bt = tables[:1]
        least = peak and mla_flops.chunk_least_seconds(W, ctx, cfg, peak)
        attempt(f"chunk {W} absorbed ctx {ctx}", lambda: timed(
            lambda q: la.latent_attention(q, pool, bt, off, None, rank=rank,
                                          sm_scale=scale), qa, reps=reps),
            least)
        for cap in (la._MAX_ROWS_EXPANDED // 2, la._MAX_ROWS_EXPANDED):
            def run(cap=cap):
                keep, la._MAX_ROWS_EXPANDED = la._MAX_ROWS_EXPANDED, cap
                try:
                    jax.clear_caches()
                    return timed(lambda q: la.latent_attention(
                        q, pool, bt, off, None, rank=rank, sm_scale=scale,
                        w_kv=(wk, wv)), qe, reps=reps)
                finally:
                    la._MAX_ROWS_EXPANDED = keep
            attempt(f"chunk {W} expanded in the walk ctx {ctx}, "
                    f"{cap // W} head(s) a program", run, least)

        pages = -(-ctx // page)

        @jax.jit
        def xla_expanded(q, pool):
            lat = pool[bt[0, :pages]].reshape(pages * page, R)
            kn = jnp.einsum("tr,hrd->thd", lat[:, :rank], wk)
            v = jnp.einsum("tr,hrd->thd", lat[:, :rank], wv)
            sc = (jnp.einsum("shd,thd->hst", q[0, :, :, :dn], kn,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("shd,td->hst", q[0, :, :, dn:],
                               lat[:, rank:rank + dr],
                               preferred_element_type=jnp.float32)) * scale
            qpos = ctx - W + jnp.arange(W)
            sc = jnp.where(jnp.arange(pages * page)[None, None, :]
                           <= qpos[None, :, None], sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
            return jnp.einsum("hst,thd->shd", p, v)

        attempt(f"chunk {W} expanded by XLA (prefix up-projected once a "
                f"chunk, dense scores) ctx {ctx}",
                lambda: timed(xla_expanded, qe, pool, reps=reps), least)

    # (c) one sublayer's mixing maps and stream products
    hc = llama.HyperConnection(mcfg)
    for rows in (W, B):
        x = jax.random.normal(ks[6], (1 if rows == W else B, mcfg.hc_mult,
                                      rows if rows == W else 1,
                                      mcfg.hidden_size)).astype(act)
        hp = hc.init(ks[7], x)

        @jax.jit
        def mix(hp, x):
            u, post, res = hc.apply(hp, x)
            return llama.hc_write(x, u, post, res)

        note(f"hc sublayer (maps, read, write) {rows} rows",
             timed(mix, hp, x, reps=reps))

    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "xing4_step0.json"), "w") as f:
        json.dump({"cell": cell.name, "device": str(
            jax.devices()[0].device_kind), "us": results}, f, indent=1)


if __name__ == "__main__":
    main()
