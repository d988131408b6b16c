"""Flash/paged-attention kernel autotune at the bench shapes.

Default mode times the pallas flash kernel (fwd and fwd+bwd) across
block_q x block_k combinations on the attached backend and prints one JSON
line per config plus a final ``best`` line.  Standalone kernel programs
compile orders of magnitude faster than the full train step, so a sweep is
cheap in chip time, and its numbers justify (or refute) the
512x512 default the models use (`ops/flash_attention.py` block_q/block_k).
``--window W`` times the sliding-window band, ``--non-causal`` the whole
square.  On a TPU each line also gives ``kernel_us``: the device-clock time
of ONE call of ``flash_fwd`` and of the backward's kernels (their events in
a profiler trace of the fwd+bwd program) — ``flash_dq_dkv``, the one call a
sequence under the kernel's VMEM budget takes, and beside it the pair it
replaces, ``flash_dq`` and ``flash_dkv``, from a second program held to the
split path (``fwd_bwd_split_ms``; past the budget the pair is all there is
and ``flash_dq_dkv`` reads None) — beside ``live_of_stepped``: the block
pairs a (batch, head) whose body runs and the steps the kernel's grid makes
for them (`ops.flash_attention.band_blocks`).

``--paged`` instead times the paged-attention kernel
(`ops/paged_attention.py`) at one serving shape with every live slot at
``--live-len`` keys, across what is left to choose: the pages one compute
step attends (``block_pages``; the kernel's own choice is a rule on the
shapes, ``walk_shape``, printed beside the fastest).  ``--chunk-width S``
(S > 1: a prefill chunk, the speculative verify) times the chunk form.
``--paged --walk`` asks what a call's time follows: the table's width is
swept at all slots live, then the live slots at one width — a walk over the
pages the slots hold is flat in the first and falls with the second.  Each
line gives ``call_us`` (host clock over chained calls, what surrounds the
kernel included) and, on a TPU, ``kernel_us`` (the kernel's own events in a
profiler trace).

Usage:
    python tools/flash_autotune.py                 # flash bench shape, TPU
    # the training cells' per-chip shapes (1 chip; a chip of tp=4: 8 / 2 heads)
    python tools/flash_autotune.py --batch 2 --heads 32 --kv-heads 8 \
        --seq 8192 --window 4096 --blocks 512
    python tools/flash_autotune.py --cpu --tiny    # flash smoke (interpret)
    python tools/flash_autotune.py --paged         # pages a step, TPU
    python tools/flash_autotune.py --paged --walk  # table width / live slots
    python tools/flash_autotune.py --paged --cpu --tiny   # paged smoke
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv", "flash_dq_dkv")


def _pct_roofline(flops: float, bytes_accessed: float, seconds: float):
    """Fraction of the device roofline a measured kernel time achieves:
    lower-bound time (compute- or bandwidth-limited, whichever dominates)
    over observed time, against the one peak table (``utils.profiling``).
    A CPU sweep (``--cpu``) has no roofline and reports None."""
    import jax

    from neuronx_distributed_tpu.utils.profiling import device_spec

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


def _paged_inputs(rs, B, NQ, NKV, D, page, PP, S, quant, dtype, live_len,
                  live_slots, pad_len=0):
    """One paged call's operands, as the serving engine presents them:
    ``live_slots`` slots hold ``live_len`` keys before the chunk behind a
    left pad of ``pad_len`` keys and a few more (a slot's own, so that bands
    start anywhere in a page), each on pages of its own; the rest are
    parked."""
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kvcache.quant import quantize_page

    T = PP * page
    spread = 3 * np.arange(B) if pad_len else np.zeros(B, np.int64)
    start = np.clip(pad_len + spread, 0, max(T - S - live_len, 0))
    off = np.where(np.arange(B) < live_slots, start + live_len, T)
    live_pages = -(-(live_len + S) // page) + 1
    NP_ = B * live_pages + 1
    q = jnp.asarray(rs.randn(B, S, NQ, D), dtype)
    kp = jnp.asarray(rs.randn(NP_, NKV, page, D), dtype)
    vp = jnp.asarray(rs.randn(NP_, NKV, page, D), dtype)
    pool = (kp, vp)
    if quant == "int8":
        qk, sk_, zk = quantize_page(kp)
        qv, sv, zv = quantize_page(vp)
        pool = (qk, qv, sk_, zk, sv, zv)
    table = np.zeros((B, PP), np.int32)
    chains = 1 + rs.permutation(NP_ - 1).reshape(B, live_pages)
    for b in range(B):
        lo = start[b] // page
        held = min(live_pages, PP - lo)
        table[b, lo:lo + held] = chains[b, :held]
    return (q, pool, jnp.asarray(table), jnp.asarray(off, jnp.int32),
            jnp.asarray(start, jnp.int32))


def _time_chain(paged_call, chain, steps, q, *rest):
    """Host-clock seconds for ONE call: ``chain`` calls in one program, each
    taking the previous one's output into its queries (times zero), so the
    host's launch is paid once per ``chain`` calls.  What sits around the
    kernel in a call (the query pad, the output slice) and the chain's own
    add are in it: tens of microseconds."""
    import jax

    def many(q_, *xs):
        out = paged_call(q_, *xs)
        for _ in range(chain - 1):
            out = paged_call(q_ + 0 * out, *xs)
        return out

    return _time_fn(jax.jit(many), steps, q, *rest) / chain


def _kernel_us(call, steps, *xs, kernels=("paged_attention",)):
    """Device-clock microseconds of a program's Pallas kernels ALONE: for
    each name in ``kernels`` the median duration of the events whose HLO
    name starts with it (and with no longer one of them: ``flash_dq_dkv`` is
    not a ``flash_dq``), in a profiler trace of ``steps`` calls (a name
    without events reads None).  One name gives a number, several a dict.
    None where there is no TPU to trace."""
    import glob
    import statistics
    import tempfile

    import jax

    if jax.devices()[0].platform != "tpu":
        return None
    fn = jax.jit(call)
    jax.block_until_ready(fn(*xs))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                out = fn(*xs)
            jax.block_until_ready(out)
        [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
    events = [(e.name.lstrip("%"), e.duration_ns)
              for plane in data.planes
              if plane.name.startswith("/device:TPU:")
              for line in plane.lines if line.name == "XLA Ops"
              for e in line.events]
    found = {}
    for kernel in kernels:
        longer = tuple(k for k in kernels if k != kernel and k.startswith(kernel))
        durations = [d for name, d in events if name.startswith(kernel)
                     and not name.startswith(longer)]
        found[kernel] = (round(statistics.median(durations) / 1e3, 1)
                         if durations else None)
    return found if len(kernels) > 1 else found[kernels[0]]


def _paged_shape(args):
    """``(B, NQ, NKV, D, page, S, quant, dtype)`` of a ``--paged`` run;
    ``--tiny`` swaps in shapes the interpreter can carry."""
    import jax.numpy as jnp

    if args.tiny:
        args.batch, args.heads, args.kv_heads = 4, 8, 2
        args.head_dim, args.steps, args.chain = 16, 2, 2
        args.page_size, args.pages_per_slot, args.live_len = 4, 8, 9
    return (args.batch, args.heads, args.kv_heads, args.head_dim,
            args.page_size, args.chunk_width,
            args.quant if args.quant != "none" else None,
            jnp.float32 if args.cpu else jnp.bfloat16)


def run_paged(args) -> int:
    """Sweep the pages a compute step attends (``block_pages``) for the
    paged kernel at one serving shape, every live slot at ``--live-len``
    keys; print one JSON line a candidate, then the fastest beside what the
    kernel's shape rule (``ops.paged_attention.walk_shape``) picks."""
    import jax
    import numpy as np

    from neuronx_distributed_tpu.ops.paged_attention import (
        paged_attention,
        walk_shape,
    )

    B, NQ, NKV, D, page, S, quant, dtype = _paged_shape(args)
    PP = args.pages_per_slot
    live = args.live_slots or B
    xs = _paged_inputs(np.random.RandomState(args.seed), B, NQ, NKV, D, page,
                       PP, S, quant, dtype, args.live_len, live, args.pad_len)
    q, pool = xs[0], xs[1]
    keys = live * min(args.live_len + S, args.window or PP * page)

    # what the call must do at least: QK^T + PV over the keys its live
    # slots attend, and those keys' pages (and its queries) through HBM once
    flops = 2 * 2 * S * NQ * keys * D
    hbm_bytes = (keys * NKV * D * 2 * pool[0].dtype.itemsize
                 + B * S * NQ * D * 2 * q.dtype.itemsize)

    heads, rule_bp = walk_shape(page, NKV, D, (NQ // NKV) * S, PP,
                                q.dtype.itemsize, pool[0].dtype.itemsize)
    shape = {"page": page, "pages_per_slot": PP, "kv_heads": NKV,
             "group": NQ // NKV, "head_dim": D, "quant": quant,
             "chunk_width": S, "slots": B, "live_slots": live,
             "live_len": args.live_len, "pad_len": args.pad_len,
             "window": args.window}
    results = []
    for bp in [c for c in (1, 2, 4, 8, 16, 32, 64) if c <= PP]:
        call = lambda q_, *r, bp=bp: paged_attention(  # noqa: E731
            q_, *r, window=args.window, block_pages=bp)
        try:
            t = _time_chain(call, args.chain, args.steps, *xs)
        except Exception as e:  # noqa: BLE001 — report and keep sweeping
            rec = {"shape": shape, "block_pages": bp, "error": str(e)[:200]}
            results.append(rec)
            print(json.dumps(rec), flush=True)
            continue
        rec = {"shape": shape, "block_pages": bp,
               "call_us": round(t * 1e6, 1),
               "kernel_us": _kernel_us(call, args.steps, *xs),
               "pct_roofline": _pct_roofline(flops, hbm_bytes, t)}
        results.append(rec)
        print(json.dumps(rec), flush=True)

    ok = [r for r in results if "error" not in r]
    if ok:
        best = min(ok, key=lambda r: r["call_us"])
        print(json.dumps({
            "best": {"block_pages": best["block_pages"],
                     "call_us": best["call_us"],
                     "pct_roofline": best["pct_roofline"]},
            "rule": {"kv_heads_per_program": heads, "block_pages": rule_bp,
                     "call_us": next((r["call_us"] for r in ok
                                      if r["block_pages"] == rule_bp), None)},
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0 if ok else 1


def run_paged_walk(args) -> int:
    """Does a call's time follow the pages its slots hold, or the pages its
    table could hold?  At the shape given (default: the chat cell's decode —
    32 slots, 28q / 4kv x 128, page 16), every live slot at ``--live-len``
    keys: the table's width swept at all slots live, then the live slots
    swept at ``--pages-per-slot``.  One JSON line a point."""
    import jax
    import numpy as np

    from neuronx_distributed_tpu.ops.paged_attention import paged_attention

    B, NQ, NKV, D, page, S, quant, dtype = _paged_shape(args)
    pad = args.pad_len + 3 * B if args.pad_len else 0  # as _paged_inputs
    need = -(-(pad + args.live_len + S) // page)
    widths = sorted({w for w in (args.pages_per_slot // 4,
                                 args.pages_per_slot // 2,
                                 args.pages_per_slot,
                                 args.pages_per_slot * 2) if w >= need})
    slots = sorted({max(1, B // 8), max(1, (B * 13) // 32), B})
    points = [(pp, B) for pp in widths] + [
        (args.pages_per_slot, n) for n in slots if n != B]
    call = lambda q_, pool, bt, off, start: paged_attention(  # noqa: E731
        q_, pool, bt, off, start, window=args.window)
    for pp, live in points:
        xs = _paged_inputs(np.random.RandomState(args.seed), B, NQ, NKV, D,
                           page, pp, S, quant, dtype, args.live_len, live,
                           args.pad_len)
        t = _time_chain(call, args.chain, args.steps, *xs)
        print(json.dumps({
            "walk": True, "pages_per_slot": pp, "live_slots": live,
            "slots": B, "live_len": args.live_len, "pad_len": args.pad_len,
            "chunk_width": S,
            "call_us": round(t * 1e6, 1),
            "kernel_us": _kernel_us(call, args.steps, *xs),
            "device": jax.devices()[0].device_kind}), flush=True)
    return 0


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
                   help="time the paged kernel across the pages a step "
                        "attends instead of the flash fwd/bwd blocks")
    p.add_argument("--page-size", type=int, default=16,
                   help="paged mode: tokens per KV page")
    p.add_argument("--pages-per-slot", type=int, default=128,
                   help="paged mode: block-table width PP (T = PP * page)")
    p.add_argument("--live-slots", type=int, default=None,
                   help="paged mode: slots not parked (default: all)")
    p.add_argument("--chunk-width", type=int, default=1,
                   help="paged mode: query rows S (1 = decode, k+1 = "
                        "speculative verify)")
    p.add_argument("--quant", default="none", choices=("none", "int8"),
                   help="paged mode: pool layout to tune")
    p.add_argument("--walk", action="store_true",
                   help="paged mode: time a call against the table's width "
                        "and against the live slots, at one live length")
    p.add_argument("--live-len", type=int, default=352,
                   help="paged mode: keys a live slot holds before the chunk")
    p.add_argument("--pad-len", type=int, default=0,
                   help="paged mode: left pad before a live slot's keys (a "
                        "few more a slot, so bands start anywhere in a page)")
    p.add_argument("--window", type=int, default=None,
                   help="sliding window (flash and paged mode)")
    p.add_argument("--non-causal", action="store_true",
                   help="flash mode: the whole square, no mask")
    p.add_argument("--chain", type=int, default=16,
                   help="paged mode: calls timed in one program")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import importlib

    # the module: ``ops`` exports the function under the same name
    fa = importlib.import_module("neuronx_distributed_tpu.ops.flash_attention")
    _block_sizes, band_blocks = fa._block_sizes, fa.band_blocks
    flash_attention = fa.flash_attention

    if args.paged:
        return run_paged_walk(args) if args.walk else run_paged(args)

    if args.tiny:
        args.batch, args.heads, args.kv_heads = 1, 2, 2
        args.seq, args.head_dim, args.steps = 64, 16, 2
        args.blocks = "16,32"

    B, HQ, HKV, S, D = args.batch, args.heads, args.kv_heads, args.seq, args.head_dim
    causal, window = not args.non_causal, args.window
    dtype = jnp.float32 if args.cpu else jnp.bfloat16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, HQ, S, D), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, HKV, S, D), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, HKV, S, D), dtype)
    # attended (query, key) pairs a (batch, head): the band, not the square
    reach = min(window or S, S)
    pairs = (reach * (reach + 1) // 2 + (S - reach) * reach) if causal else S * S
    flops = 2 * 2 * B * HQ * pairs * D  # forward: 2 matmuls x 2 flops
    # streamed bytes: q in + o out (HQ) and k + v in (HKV)
    fbytes = (B * HQ * S * D * 2 + B * HKV * S * D * 2) * q.dtype.itemsize

    blocks = [int(b) for b in args.blocks.split(",")]
    results = []
    for bq, bk in itertools.product(blocks, blocks):
        attend = lambda a, b_, c, bq=bq, bk=bk: flash_attention(  # noqa: E731
            a, b_, c, causal, None, bq, bk, None, window)
        fwd = jax.jit(attend)

        def grad():  # traced anew a call: the backward reads the budget then
            return jax.jit(jax.grad(
                lambda a, b_, c: attend(a, b_, c).astype(jnp.float32).sum(),
                (0, 1, 2)))

        fused = fa._dq_rows_vmem(S, D) <= fa._FUSED_DQ_BYTES
        try:
            t_fwd = _time_fn(fwd, args.steps, q, k, v)
            bwd = grad()
            t_bwd = _time_fn(bwd, args.steps, q, k, v)
            kernel_us = _kernel_us(bwd, args.steps, q, k, v,
                                   kernels=FLASH_KERNELS)
            t_split = None
            if fused:  # the pair it replaces, timed beside it
                budget, fa._FUSED_DQ_BYTES = fa._FUSED_DQ_BYTES, 0
                try:
                    split = grad()
                    t_split = _time_fn(split, args.steps, q, k, v)
                    split_us = _kernel_us(split, args.steps, q, k, v,
                                          kernels=FLASH_KERNELS)
                finally:
                    fa._FUSED_DQ_BYTES = budget
                if kernel_us is not None:
                    kernel_us.update(flash_dq=split_us["flash_dq"],
                                     flash_dkv=split_us["flash_dkv"])
        except Exception as e:  # noqa: BLE001 — report and continue sweeping
            rec = {"block_q": bq, "block_k": bk, "error": str(e)[:200]}
            results.append(rec)
            print(json.dumps(rec), flush=True)
            continue
        # block pairs a (batch, head) whose body runs, of the grid's steps
        fitted = _block_sizes(S, S, bq, bk)
        by_q = band_blocks(S, S, *fitted, causal, window)
        by_kv = band_blocks(S, S, *fitted, causal, window, by_kv=True)
        bands = (by_q, by_q, by_kv, by_kv)  # flash_dq_dkv walks flash_dkv's
        rec = {
            "shape": {"batch": B, "heads": HQ, "kv_heads": HKV, "seq": S,
                      "head_dim": D, "causal": causal, "window": window},
            "block_q": bq, "block_k": bk,
            "fwd_ms": round(t_fwd * 1e3, 3),
            "fwd_bwd_ms": round(t_bwd * 1e3, 3),
            "fwd_bwd_split_ms": t_split and round(t_split * 1e3, 3),
            "kernel_us": kernel_us,
            "live_of_stepped": {kernel: [band.live, band.stepped]
                                for kernel, band in zip(FLASH_KERNELS, bands)},
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
