#!/usr/bin/env python3
"""What ONE layer's K + V pool write costs on the chip, form against form.

At each serving cell's paged geometry (slots x kv heads, pool pages; page 16,
head_dim 128, bf16) a program writes a step's new K and V rows into the
donated pools and attends over them with ``paged_attention`` — ``--chain``
times over, each call's queries taking the previous output (times zero) —
for a decode (one row a slot, every slot live) and for a 512-row prefill
chunk (one slot).  The same program without the write is timed beside it;
the difference, on the DEVICE's clock (the union of the program's operations
in a profiler trace, the paged kernel's own events left out), is the write.

Forms: ``scatter`` (the split-index row scatter the models used up to PR 27),
``rows`` (the same rows addressed in the pool seen flat), ``pages``
(``ops.kv_pool_write``'s XLA form) and ``kernel`` (its Pallas call).  Every
form's pools are compared with ``scatter``'s, bit for bit.

    python tools/kv_write_bench.py            # on the chip machine
    python tools/kv_write_bench.py --tiny     # CPU rehearsal: bits only
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAGE, D = 16, 128
# cell: (slots, kv heads, group, pages a slot, pool pages, window)
CELLS = {
    "qwen2-7b.serve-chat": (32, 4, 7, 128, 4353, None),
    "mistral-7b.serve-docs": (8, 8, 4, 512, 4161, 4096),
    "olmoe-1b-7b.serve-backlog": (16, 16, 1, 64, 1153, None),
}
TINY = {"tiny": (4, 2, 2, 8, 40, None)}


def _forms():
    import jax.numpy as jnp

    from neuronx_distributed_tpu.ops.kv_pool_write import write_pool_rows

    def scatter(pool, new, phys, in_off):
        return pool.at[phys, :, in_off].set(new.astype(pool.dtype),
                                            mode="drop")

    def rows(pool, new, phys, in_off):
        NP, NKV, page, d = pool.shape
        r = (phys[..., None] * NKV + jnp.arange(NKV)) * page + in_off[..., None]
        r = jnp.where(phys[..., None] < NP, r, NP * NKV * page)
        flat = pool.reshape(NP * NKV * page, d).at[r.reshape(-1)].set(
            new.astype(pool.dtype).reshape(-1, d), mode="drop")
        return flat.reshape(pool.shape)

    return {"scatter": scatter, "rows": rows, "pages": write_pool_rows,
            "kernel": functools.partial(write_pool_rows, kernel=True)}


def _program(write, chain, num_pages, pp, window):
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.ops.paged_attention import paged_attention

    def prog(q, k, v, pool, bt, off):
        ck, cv = pool
        S = k.shape[1]
        idx = off[:, None] + jnp.arange(S)[None, :]
        phys = jnp.take_along_axis(bt, jnp.clip(idx // PAGE, 0, pp - 1), axis=1)
        phys = jnp.where(idx < pp * PAGE, phys, num_pages)
        out = jnp.zeros_like(q)
        for _ in range(chain):
            if write is not None:
                with jax.named_scope("kv_write"):
                    ck = write(ck, k + (0 * out[:, :, :k.shape[2]]), phys,
                               idx % PAGE)
                    cv = write(cv, v, phys, idx % PAGE)
            out = paged_attention(q + 0 * out, (ck, cv), bt, off, None,
                                  window=window)
        return out, (ck, cv)

    return jax.jit(prog, donate_argnums=(3,))


def _device_us(fn, steps, args, pool):
    """Microseconds a call keeps the device busy outside the paged kernel:
    the union of its operations' intervals in a trace of ``steps`` calls."""
    import jax

    out, pool = fn(*args[:3], pool, *args[3:])
    jax.block_until_ready(out)
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                out, pool = fn(*args[:3], pool, *args[3:])
            jax.block_until_ready(out)
        [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
    spans = sorted(
        (e.start_ns, e.start_ns + e.duration_ns)
        for plane in data.planes if plane.name.startswith("/device:TPU:0")
        for line in plane.lines if line.name == "XLA Ops"
        for e in line.events if "paged_attention" not in e.name)
    busy, end = 0.0, 0.0
    for lo, hi in spans:
        if hi > end:
            busy += hi - max(lo, end)
            end = hi
    return busy / 1e3 / steps, pool


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true",
                   help="small shapes on the CPU: the bits, no times")
    p.add_argument("--chain", type=int, default=8)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--chunk", type=int, default=512)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    timed = jax.devices()[0].platform == "tpu"
    if not (timed or args.tiny):
        print("kv_write_bench: no TPU here (--tiny rehearses the bits on "
              "the CPU)", file=sys.stderr)
        return 1
    forms = _forms()
    rs = np.random.RandomState(args.seed)
    ok = True
    for cell, (B, nkv, group, pp, num_pages, window) in (
            TINY if args.tiny else CELLS).items():
        T = pp * PAGE
        chunk = min(args.chunk, T // 2)
        for S, slots in ((1, B), (chunk, 1)):
            # decode: every slot mid-context at an offset of its own; chunk:
            # one slot, page-aligned, as the engine launches it
            off = (T // 4 + 3 * np.arange(slots) if S == 1
                   else np.full(slots, T // 4 // PAGE * PAGE))
            table = 1 + np.arange(slots * pp).reshape(slots, pp) % (num_pages - 1)
            q = jnp.asarray(rs.randn(slots, S, nkv * group, D), jnp.bfloat16)
            k = jnp.asarray(rs.randn(slots, S, nkv, D), jnp.bfloat16)
            v = jnp.asarray(rs.randn(slots, S, nkv, D), jnp.bfloat16)
            rest = (jnp.asarray(table, jnp.int32), jnp.asarray(off, jnp.int32))
            pool0 = np.asarray(rs.randn(num_pages, nkv, PAGE, D), np.float32)

            def pools():
                return (jnp.asarray(pool0, jnp.bfloat16),
                        jnp.asarray(-pool0, jnp.bfloat16))

            line = {"cell": cell, "S": S, "slots": slots, "chain": args.chain}
            base_us = None
            if timed:
                base_us, _ = _device_us(
                    _program(None, args.chain, num_pages, pp, window),
                    args.steps, (q, k, v) + rest, pools())
                line["attend_only_us"] = round(base_us / args.chain, 2)
            want = None
            for name, write in forms.items():
                fn = _program(write, args.chain, num_pages, pp, window)
                pool = pools()
                if timed:
                    us, pool = _device_us(fn, args.steps, (q, k, v) + rest, pool)
                    line[f"{name}_us"] = round((us - base_us) / args.chain, 2)
                else:
                    _, pool = fn(q, k, v, pool, *rest)
                got = [np.asarray(x).view(np.uint16) for x in pool]
                if want is None:
                    want = got
                same = all((a == b).all() for a, b in zip(want, got))
                line[f"{name}_bits"] = same
                ok &= same
            print(json.dumps(line), flush=True)
    d = jax.devices()[0]
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": d.platform, "device_kind": d.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
