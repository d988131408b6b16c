"""Flash/paged-attention kernel autotune at the bench shapes.

Default mode times the pallas flash kernel (fwd and fwd+bwd) across
block_q x block_k combinations on the attached backend and prints one JSON
line per config plus a final ``best`` line.  Standalone kernel programs
compile orders of magnitude faster than the full train step, so a sweep is
cheap in chip time, and its numbers justify (or refute) the
512x512 default the models use (`ops/flash_attention.py` block_q/block_k).

``--paged`` instead sweeps the paged-attention DECODE kernel
(`ops/paged_attention.py`) across (block_pages, split_k) candidates for one
(page, pages_per_slot, kv_heads, head_dim, quant) shape key and prints a
``defaults_entry`` line in exactly the `SHAPE_DEFAULTS` table format the
kernel consults — run it per serving shape on silicon and commit the
winning entries.  With ``--chunk-width S`` (S > 1: in-kernel chunked
prefill and speculative verify) the key grows a sixth element and the
``defaults_entry`` targets the `CHUNK_SHAPE_DEFAULTS` table instead —
wide chunks amortize grid overhead differently, so they get their own
committed entries rather than reusing the S = 1 decode winner.

Usage:
    python tools/flash_autotune.py                 # flash bench shape, TPU
    python tools/flash_autotune.py --cpu --tiny    # flash smoke (interpret)
    python tools/flash_autotune.py --paged         # paged decode sweep, TPU
    python tools/flash_autotune.py --paged --cpu --tiny   # paged smoke
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pct_roofline(flops: float, bytes_accessed: float, seconds: float):
    """Fraction of the device roofline a measured kernel time achieves:
    lower-bound time (compute- or bandwidth-limited, whichever dominates)
    over observed time, against the one peak table (``obs.perf``).  A CPU
    sweep (``--cpu``) has no roofline and reports None."""
    import jax

    from neuronx_distributed_tpu.obs.perf import device_spec

    if jax.devices()[0].platform == "cpu":
        return None
    spec = device_spec()
    lower = max(flops / spec.peak_flops, bytes_accessed / spec.hbm_bytes_per_s)
    return round(lower / seconds, 4) if seconds > 0 else 0.0


def _time_fn(f, steps, *xs):
    import statistics
    import time as _time

    import jax

    out = f(*xs)
    jax.block_until_ready(out)
    ts = []
    for _ in range(steps):
        t0 = _time.perf_counter()
        out = f(*xs)
        jax.block_until_ready(out)
        ts.append(_time.perf_counter() - t0)
    return statistics.median(ts)


def run_paged(args) -> int:
    """Sweep (block_pages, split_k) for the paged decode kernel at one
    serving shape key; print one JSON line per candidate plus the winning
    ``defaults_entry`` in `ops.paged_attention.SHAPE_DEFAULTS` format."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kvcache.quant import quantize_page
    from neuronx_distributed_tpu.ops.paged_attention import paged_attention

    if args.tiny:
        args.batch, args.heads, args.kv_heads = 4, 8, 2
        args.head_dim, args.steps = 16, 2
        args.page_size, args.pages_per_slot = 4, 8
        args.num_pages = 64

    B, NQ, NKV, D = args.batch, args.heads, args.kv_heads, args.head_dim
    page, PP = args.page_size, args.pages_per_slot
    S = args.chunk_width
    NP_ = args.num_pages or (B * PP + 1)
    quant = args.quant if args.quant != "none" else None
    T = PP * page

    rs = np.random.RandomState(args.seed)
    dtype = jnp.float32 if args.cpu else jnp.bfloat16
    q = jnp.asarray(rs.randn(B, S, NQ, D), dtype)
    kp = jnp.asarray(rs.randn(NP_, NKV, page, D), dtype)
    vp = jnp.asarray(rs.randn(NP_, NKV, page, D), dtype)
    if quant == "int8":
        qk, sk_, zk = quantize_page(kp)
        qv, sv, zv = quantize_page(vp)
        pool = (qk, qv, sk_, zk, sv, zv)
    else:
        pool = (kp, vp)
    bt = jnp.asarray(rs.randint(1, NP_, size=(B, PP)), jnp.int32)
    # decode at a full chain — the worst case the defaults must win at
    off = jnp.full((B,), T - S, jnp.int32)
    start = jnp.zeros((B,), jnp.int32)

    def divisors(n, cands):
        return [c for c in cands if c <= n and n % c == 0]

    # decode attention cost at the swept shape (identical for every
    # candidate — only the achieved time varies): QK^T + PV over the full
    # chain per query row, and the kernel must stream every mapped page
    kv_bytes = 1 if quant == "int8" else q.dtype.itemsize
    dec_flops = 2 * 2 * B * S * NQ * T * D
    dec_bytes = (B * PP * page * NKV * D * 2 * kv_bytes
                 + B * S * NQ * D * 2 * q.dtype.itemsize)

    bps = divisors(PP, [1, 2, 4, 8, 16])
    results = []
    # S = 1 tunes the decode table; S > 1 (chunked prefill / spec verify)
    # tunes the six-tuple CHUNK_SHAPE_DEFAULTS key at this pool geometry
    key = [page, PP, NKV, D, quant] + ([S] if S > 1 else [])
    table = "CHUNK_SHAPE_DEFAULTS" if S > 1 else "SHAPE_DEFAULTS"
    for bp in bps:
        for sk in divisors(PP // bp, [1, 2, 4, 8]):
            fn = jax.jit(lambda q_, bp=bp, sk=sk: paged_attention(
                q_, pool, bt, off, start, block_pages=bp, split_k=sk))
            try:
                t = _time_fn(fn, args.steps, q)
            except Exception as e:  # noqa: BLE001 — report and keep sweeping
                rec = {"shape_key": key, "block_pages": bp, "split_k": sk,
                       "error": str(e)[:200]}
                results.append(rec)
                print(json.dumps(rec), flush=True)
                continue
            rec = {"shape_key": key, "block_pages": bp, "split_k": sk,
                   "decode_ms": round(t * 1e3, 3),
                   "pct_roofline": _pct_roofline(dec_flops, dec_bytes, t)}
            results.append(rec)
            print(json.dumps(rec), flush=True)

    ok = [r for r in results if "error" not in r]
    if ok:
        best = min(ok, key=lambda r: r["decode_ms"])
        # the defaults-table entry to commit (ops/paged_attention.py)
        print(json.dumps({
            "defaults_entry": {
                "table": table,
                "key": key,
                "block_pages": best["block_pages"],
                "split_k": best["split_k"],
            },
            "decode_ms": best["decode_ms"],
            "pct_roofline": best["pct_roofline"],
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0 if ok else 1


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--kv-heads", type=int, default=12)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument("--blocks", default="256,512,1024")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--tiny", action="store_true", help="smoke shapes")
    p.add_argument("--paged", action="store_true",
                   help="sweep the paged decode kernel (block_pages x "
                        "split_k) instead of the flash fwd/bwd blocks")
    p.add_argument("--page-size", type=int, default=16,
                   help="paged mode: tokens per KV page")
    p.add_argument("--pages-per-slot", type=int, default=128,
                   help="paged mode: block-table width PP (T = PP * page)")
    p.add_argument("--num-pages", type=int, default=None,
                   help="paged mode: physical pool pages (default B*PP+1)")
    p.add_argument("--chunk-width", type=int, default=1,
                   help="paged mode: query rows S (1 = decode, k+1 = "
                        "speculative verify)")
    p.add_argument("--quant", default="none", choices=("none", "int8"),
                   help="paged mode: pool layout to tune")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from neuronx_distributed_tpu.ops.flash_attention import flash_attention

    if args.paged:
        return run_paged(args)

    if args.tiny:
        args.batch, args.heads, args.kv_heads = 1, 2, 2
        args.seq, args.head_dim, args.steps = 64, 16, 2
        args.blocks = "16,32"

    B, HQ, HKV, S, D = args.batch, args.heads, args.kv_heads, args.seq, args.head_dim
    dtype = jnp.float32 if args.cpu else jnp.bfloat16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, HQ, S, D), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, HKV, S, D), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, HKV, S, D), dtype)
    # causal attention FLOPs: 2 matmuls x 2 flops, half the square
    flops = 2 * 2 * B * HQ * S * S * D / 2
    # streamed bytes: q in + o out (HQ) and k + v in (HKV)
    fbytes = (B * HQ * S * D * 2 + B * HKV * S * D * 2) * q.dtype.itemsize

    blocks = [int(b) for b in args.blocks.split(",")]
    results = []
    for bq, bk in itertools.product(blocks, blocks):
        fwd = jax.jit(lambda a, b_, c, bq=bq, bk=bk: flash_attention(
            a, b_, c, True, None, bq, bk))
        grad = jax.jit(jax.grad(lambda a, b_, c, bq=bq, bk=bk: flash_attention(
            a, b_, c, True, None, bq, bk).astype(jnp.float32).sum(), (0, 1, 2)))

        try:
            t_fwd = _time_fn(fwd, args.steps, q, k, v)
            t_bwd = _time_fn(grad, args.steps, q, k, v)
        except Exception as e:  # noqa: BLE001 — report and continue sweeping
            rec = {"block_q": bq, "block_k": bk, "error": str(e)[:200]}
            results.append(rec)
            print(json.dumps(rec), flush=True)
            continue
        rec = {
            "block_q": bq, "block_k": bk,
            "fwd_ms": round(t_fwd * 1e3, 3),
            "fwd_bwd_ms": round(t_bwd * 1e3, 3),
            "fwd_tflops": round(flops / t_fwd / 1e12, 2),
            "pct_roofline": _pct_roofline(flops, fbytes, t_fwd),
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)

    ok = [r for r in results if "error" not in r]
    if ok:
        best = min(ok, key=lambda r: r["fwd_bwd_ms"])
        print(json.dumps({"best": best,
                          "device": jax.devices()[0].device_kind}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
