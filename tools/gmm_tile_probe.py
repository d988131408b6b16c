#!/usr/bin/env python
"""gmm_tile_probe.py — which megablox tile serves each routed block's widths.

    chiprun -- python tools/gmm_tile_probe.py [--blocks deepseek-v2,...] [--no-sweep]

For every routed configuration under ``benchmarks/configs/`` (OLMoE,
Nemotron-3-Nano, Xing4.0, DeepSeek-V2: published hidden and expert widths,
the held expert count, the layout the program stores — ``transpose_rhs`` for
Nemotron's ``up [E, I, H]``) and two row counts (the cell's decode and its
512-row chunk, group sizes drawn near uniform over the held experts, the
rows of absent experts past every group as the program leaves them) it
times each grouped matmul ALONE and then the expert block, under today's
tile (``min`` of ``(128, 2048, 1024)`` and the dimension), under
``parallel.moe.gmm_tile``'s choice, and under every ``tk`` that is a
multiple of 128 and divides K crossed with ``tn`` in {512, 768, 896, 1024,
N}: the question is what upstream's ``mask_k_rem`` (the last k-tile through
float32 on the vector unit whenever ``k % tk != 0``) costs.  Method as
``benchmarks/tools/moe_gmm_probe.py``: a plain per-expert loop is the check,
host clock around ``block_until_ready`` (five calls queued back to back a
sample, so a launch's host time hides), median of 20 samples after a warm
call, the byte and FLOP floors from ``benchmarks/harness/peaks.json``.  A
tile the compiler refuses is a row that says so.  Rows are printed as they
come and written to ``chiprun_out/gmm_tile_probe.json``.  ``--rehearse``
runs a toy shape through the interpreter on the CPU (no number of it is a
device number).

``--backward`` asks the TRAINING question instead (PR 43): at ``--rows`` rows
over ``--groups`` held experts of ``--widths H,I`` (default LFM2-8B-A1B's
16,384 rows over 8 experts of 2048 x 1792) it times, for the gate/up shape
``[G, H, I]`` and the down shape ``[G, I, H]``, the forward ``gmm`` under
``gmm_tile``, the data gradient (``gmm`` with the right-hand side flipped)
and the weight gradient (``tgmm``) under ``gmm_backward_tiles``' choice and
under every dividing tile tried, each against ``2 x rows x H x I`` FLOPs at
the bf16 peak; rows go to ``chiprun_out/gmm_backward_probe.json``.
"""

import argparse
import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TODAY = (128, 2048, 1024)
TN_TRIED = (512, 768, 896, 1024)
# (rows the program hands the kernel, rows that land on a held expert): a
# decode step's slots x top-k and a 512-row chunk's, the held share as the
# cells' counters read it (PERF.md §5)
ROWS = {
    "olmoe-1b-7b": {"decode": (128, 128), "chunk": (4096, 4096)},
    "nemotron-3-nano-30b-a3b": {"decode": (384, 192), "chunk": (3072, 1536)},
    "xing4.0-29b-a4b": {"decode": (32, 32), "chunk": (2048, 2048)},
    "deepseek-v2": {"decode": (192, 24), "chunk": (3072, 390)},
}


def routed_blocks():
    """``{name: dict(E, H, I, gated)}`` from the benchmark's configurations."""
    out = {}
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmarks", "configs", "*.json"))):
        kw = json.load(open(path))["program"]["kwargs"]
        if kw.get("moe_dispatch") != "dropless" or ".serve" not in path:
            continue
        held = kw.get("moe_experts_held")
        out[os.path.basename(path).split(".serve")[0]] = dict(
            E=held[1] if held else kw["num_experts"], H=kw["hidden_size"],
            I=kw.get("moe_intermediate_size", kw["intermediate_size"]),
            gated=kw.get("mlp_activation") != "relu2")
    return out


def matmuls(H, I, gated):
    """``[(name, K, N, transpose_rhs)]`` of one expert block, as stored."""
    if gated:
        return [("gate", H, I, False), ("up", H, I, False),
                ("down", I, H, False)]
    return [("up", H, I, True), ("down", I, H, False)]


def today_tile(k, n):
    """What ``grouped_matmul`` passed until PR 41."""
    return TODAY[0], min(TODAY[1], k), min(TODAY[2], n)


def candidates(k, n, chosen, sweep=True):
    """Today's tile, the rule's, then the dividing ``tk`` x the tried ``tn``."""
    today = today_tile(k, n)
    out = [today] + ([chosen] if chosen != today else [])
    if not sweep:
        return out
    tks = [t for t in range(k, 0, -128) if t % 128 == 0 and k % t == 0] \
        if k % 128 == 0 else [k]
    for tk in tks:
        if tk < 512 and tk != k:     # short k-steps: the grid's overhead
            continue
        for tn in sorted({min(t, n) for t in TN_TRIED} | {n}):
            if (128, tk, tn) not in out:
                out.append((128, tk, tn))
    return out


def near_uniform_sizes(rs, E, held_rows):
    """Group sizes as a balanced router gives them: every row picks one of
    ``E`` experts at random (max over mean ~1.2-2 at these counts)."""
    import numpy as np
    return np.bincount(rs.randint(0, E, size=held_rows),
                       minlength=E).astype(np.int32)


def _dividing(x, least=256):
    """Multiples of 128 from ``least`` up that divide ``x``, largest first."""
    return [t for t in range(x, least - 1, -128) if x % t == 0]


def backward_probe(args):
    """The three grouped matmuls of a TRAINED expert's one weight, timed
    alone (module docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import manifest
    from neuronx_distributed_tpu.parallel import moe

    backend = moe._megablox()       # the module that holds both kernels
    dev = jax.devices()[0]
    H, I = (int(x) for x in args.widths.split(","))
    M, G = args.rows, args.groups
    if args.rehearse:
        peak, samples, queued = {"bf16_flops_per_s": 1.0}, 1, 1
        H, I, M, G = 256, 384, 512, 3
    else:
        if dev.platform != "tpu":
            sys.exit(f"gmm_tile_probe measures a TPU; found {dev.platform}")
        peak, samples, queued = manifest.peaks_for(str(dev.device_kind)), 10, 3
    dt = jnp.float32 if args.rehearse else jnp.bfloat16
    item = jnp.dtype(dt).itemsize
    rs = np.random.RandomState(M)
    sizes_np = near_uniform_sizes(rs, G, M - M // 16)   # a tail in no group
    if G > 2:
        sizes_np[1] += sizes_np[2]; sizes_np[2] = 0     # an empty group
    sizes = jnp.asarray(sizes_np)
    live = int(sizes_np.sum())
    bounds = np.concatenate([[0], np.cumsum(sizes_np)])
    table = []

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        ts = []
        for _ in range(samples):
            t0 = time.perf_counter()
            for _ in range(queued):
                out = fn(*a)
            jax.block_until_ready(out)
            ts.append((time.perf_counter() - t0) / queued)
        return float(np.median(ts) * 1e3)

    def run(row, fn, ref, *a):
        try:
            fn = jax.jit(fn)
            out = np.asarray(fn(*a), np.float32)
            row["rel_err"] = float(np.max(np.abs(out - ref))
                                   / (np.max(np.abs(ref)) + 1e-9))
            row["ms"] = timed(fn, *a)
            row["share_of_peak"] = 100 * row["least_ms"] / row["ms"]
        except Exception as e:  # a refused tile is a row
            row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        table.append(row)
        print(json.dumps(row), flush=True)

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    for K, N in ((H, I), (I, H)):
        x = jax.random.normal(keys[0], (M, K), jnp.float32).astype(dt)
        g = jax.random.normal(keys[1], (M, N), jnp.float32).astype(dt)
        w = (jax.random.normal(keys[2], (G, K, N), jnp.float32)
             * 0.02).astype(dt)
        xf, gf, wf = (np.asarray(t, np.float32) for t in (x, g, w))
        fwd_ref, dx_ref = np.zeros((M, N), np.float32), np.zeros((M, K), np.float32)
        dw_ref = np.zeros((G, K, N), np.float32)
        for e in range(G):
            a, b = bounds[e], bounds[e + 1]
            fwd_ref[a:b] = xf[a:b] @ wf[e]
            dx_ref[a:b] = gf[a:b] @ wf[e].T
            dw_ref[e] = xf[a:b].T @ gf[a:b]
        least = 2.0 * live * K * N / peak["bf16_flops_per_s"] * 1e3
        dlhs, drhs = moe.gmm_backward_tiles(M, K, N, item)
        base = dict(rows=M, live=live, groups=G, k=K, n=N, least_ms=least)
        for tm in (128, 256, 512):
            t = (tm,) + moe.gmm_tile(M, K, N, item)[1:]
            run(dict(base, op="gmm", tiling=list(t), chosen=tm == 128),
                lambda x, w, s, t=t: backend.gmm(
                    x, w, s, dt, t, interpret=args.rehearse)[:live],
                fwd_ref[:live], x, w, sizes)
        tried = [dlhs] + [(tm, tk, tn) for tm in (128, 256, 512)
                          for tk in _dividing(N, 512)[:2]
                          for tn in _dividing(K, 512)[:3]]
        for t in dict.fromkeys(tried):
            run(dict(base, op="gmm_flipped", tiling=list(t), chosen=t == dlhs),
                lambda g, w, s, t=t: backend.gmm(
                    g, w, s, dt, t, transpose_rhs=True,
                    interpret=args.rehearse)[:live],
                dx_ref[:live], g, w, sizes)
        tried = [drhs] + [(tm, tk, tn) for tm in (128, 256, 512, 1024)
                          for tk in _dividing(K)[:3] for tn in _dividing(N)[:3]]
        for t in dict.fromkeys(tried):
            run(dict(base, op="tgmm", tiling=list(t), chosen=t == drhs),
                lambda x, g, s, t=t: backend.tgmm(
                    x.swapaxes(0, 1), g, s, dt, t, interpret=args.rehearse),
                dw_ref, x, g, sizes)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gmm_backward_probe.json"),
              "w") as f:
        json.dump(dict(device=str(dev.device_kind), rows=table), f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="", help="comma list; default all")
    ap.add_argument("--no-sweep", action="store_true",
                    help="today's tile and the rule's only")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--backward", action="store_true",
                    help="the training question: flipped gmm and tgmm tiles")
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--widths", default="2048,1792", help="H,I")
    args = ap.parse_args()
    if args.backward:
        return backward_probe(args)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from benchmarks.harness import manifest
    from neuronx_distributed_tpu.parallel import moe

    dev = jax.devices()[0]
    if args.rehearse:
        peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
        blocks = {"toy": dict(E=3, H=384, I=256, gated=True),
                  "toy-relu2": dict(E=3, H=384, I=192, gated=False)}
        rows_of = {b: {"decode": (24, 12)} for b in blocks}
        samples, queued = 1, 1
    else:
        if dev.platform != "tpu":
            sys.exit(f"gmm_tile_probe measures a TPU; found {dev.platform} "
                     "(--rehearse runs the interpreter on a toy shape)")
        peak = manifest.peaks_for(str(dev.device_kind))
        blocks, rows_of = routed_blocks(), ROWS
        samples, queued = 20, 5
    want = [b for b in args.blocks.split(",") if b] or list(blocks)

    def mm(tiling, transpose_rhs):
        def f(x, w, sizes):
            return gmm(x, w, sizes, preferred_element_type=x.dtype,
                       tiling=tiling, transpose_rhs=transpose_rhs,
                       interpret=args.rehearse)
        return f

    def timed(fn, *a):
        fn(*a).block_until_ready()
        ts = []
        for _ in range(samples):
            t0 = time.perf_counter()
            for _ in range(queued):
                out = fn(*a)
            out.block_until_ready()
            ts.append((time.perf_counter() - t0) / queued)
        return float(np.median(ts) * 1e3)

    def loop_ref(x, w, sizes_np, transpose_rhs):
        ref = np.zeros((x.shape[0], w.shape[1 if transpose_rhs else 2]),
                       np.float32)
        xf, start = np.asarray(x, np.float32), 0
        for e, cnt in enumerate(sizes_np):
            if cnt:
                we = np.asarray(w[e], np.float32)
                ref[start:start + cnt] = xf[start:start + cnt] @ (
                    we.T if transpose_rhs else we)
            start += cnt
        return ref

    table = []

    def emit(**row):
        table.append(row)
        print(json.dumps(row), flush=True)

    dt = jnp.float32 if args.rehearse else jnp.bfloat16
    item = jnp.dtype(dt).itemsize
    for name in want:
        b = blocks[name]
        E, H, I = b["E"], b["H"], b["I"]
        mms = matmuls(H, I, b["gated"])
        keys = jax.random.split(jax.random.PRNGKey(0), len(mms) + 1)
        ws = {}
        for (mname, k, n, tr), key in zip(mms, keys):
            shape = (E, n, k) if tr else (E, k, n)
            ws[mname] = (jax.random.normal(key, shape, jnp.float32)
                         * 0.02).astype(dt)
        for phase, (m_rows, held_rows) in rows_of[name].items():
            rs = np.random.RandomState(m_rows)
            sizes_np = near_uniform_sizes(rs, E, held_rows)
            sizes = jnp.asarray(sizes_np)
            hit = int((sizes_np > 0).sum())
            m = m_rows + (-m_rows % 128)
            best = {}
            for mname, k, n, tr in mms:
                if mname == "up" and b["gated"]:    # gate's shape and layout
                    best["up"] = best["gate"]
                    continue
                x = jax.random.normal(keys[-1], (m, k), jnp.float32
                                      ).astype(dt)
                ref = loop_ref(x, ws[mname], sizes_np, tr)
                live = int(sizes_np.sum())
                least = max(2.0 * held_rows * k * n / peak["bf16_flops_per_s"],
                            (hit * k * n + held_rows * (k + n)) * item
                            / peak["hbm_bytes_per_s"]) * 1e3
                chosen = moe.gmm_tile(m, k, n, item)
                for tiling in candidates(k, n, chosen, not args.no_sweep):
                    row = dict(block=name, phase=phase, matmul=mname, m=m,
                               held_rows=held_rows, experts_hit=hit, k=k, n=n,
                               transpose_rhs=tr, tiling=list(tiling),
                               k_rem=k % tiling[1], least_ms=least,
                               today=tiling == today_tile(k, n),
                               chosen=tiling == chosen)
                    try:
                        fn = jax.jit(mm(tiling, tr))
                        out = fn(x, ws[mname], sizes)
                        row["rel_err"] = float(np.max(np.abs(
                            np.asarray(out[:live], np.float32) - ref[:live]))
                            / (np.max(np.abs(ref)) + 1e-9))
                        row["ms"] = timed(fn, x, ws[mname], sizes)
                        row["share_of_roofline"] = 100 * least / row["ms"]
                        if mname not in best or row["ms"] < best[mname][1]:
                            best[mname] = (tiling, row["ms"])
                    except Exception as e:  # a refused tile is a row
                        row["error"] = f"{type(e).__name__}: {str(e)[:160]}"
                    emit(**row)
            # the block: gate/up -> activation -> down, under today's tile,
            # the rule's and each matmul's fastest
            x = jax.random.normal(keys[-1], (m, H), jnp.float32).astype(dt)

            def block(tile_of):
                def f(x, sizes, *w):
                    wd = dict(zip([q[0] for q in mms], w))
                    if b["gated"]:
                        gate = mm(tile_of("gate", H, I), False)(
                            x, wd["gate"], sizes)
                        up = mm(tile_of("up", H, I), False)(x, wd["up"], sizes)
                        h = jax.nn.silu(gate) * up
                    else:
                        h = jnp.square(jax.nn.relu(mm(
                            tile_of("up", H, I), True)(x, wd["up"], sizes)))
                    return mm(tile_of("down", I, H), False)(
                        h, wd["down"], sizes)
                return jax.jit(f)

            choices = {
                "today": lambda q, k, n: today_tile(k, n),
                "chosen": lambda q, k, n: moe.gmm_tile(m, k, n, item),
                "fastest": lambda q, k, n: best[q][0],
            }
            nbytes = sum(hit * k * n for _, k, n, _ in mms) * item
            flops = sum(2.0 * held_rows * k * n for _, k, n, _ in mms)
            least = max(flops / peak["bf16_flops_per_s"],
                        nbytes / peak["hbm_bytes_per_s"]) * 1e3
            for label, tile_of in choices.items():
                row = dict(block=name, phase=phase, matmul="block", m=m,
                           held_rows=held_rows, experts_hit=hit, tiles=label,
                           tilings={q[0]: list(tile_of(q[0], q[1], q[2]))
                                    for q in mms}, least_ms=least)
                try:
                    row["ms"] = timed(block(tile_of), x, sizes,
                                      *[ws[q[0]] for q in mms])
                    row["share_of_roofline"] = 100 * least / row["ms"]
                except Exception as e:
                    row["error"] = f"{type(e).__name__}: {str(e)[:160]}"
                emit(**row)
        del ws
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gmm_tile_probe.json"),
              "w") as f:
        json.dump(dict(device=str(dev.device_kind), rows=table), f, indent=1)


if __name__ == "__main__":
    main()
