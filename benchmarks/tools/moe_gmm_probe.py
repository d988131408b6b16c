#!/usr/bin/env python
"""moe_gmm_probe.py — which grouped matmul serves OLMoE's expert block on the chip.

    chiprun -- python benchmarks/tools/moe_gmm_probe.py

Times one expert block (gate-up grouped matmul -> silu * up -> down grouped
matmul over rows sorted by expert) at OLMoE-1B-7B's widths (64 experts,
hidden 2048, expert width 1024, 8 experts a token) for a decode step's 16
rows and a prefill chunk's 512, with ``jax.lax.ragged_dot`` and with
``megablox.gmm`` under a few tilings, against the weight-read and FLOP
floors of the table of peaks.  Host clock around ``block_until_ready``,
median of 20 after a warm call; every candidate is checked against a plain
per-expert loop first.  PR 25 chose the program's primitive from this table
(``PERF.md``, Findings, PR 25).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

E, H, I, K = 64, 2048, 1024, 8


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from benchmarks.harness import manifest

    dev = jax.devices()[0]
    peak = manifest.peaks_for(str(dev.device_kind))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    wi = (jax.random.normal(k1, (E, H, 2 * I), jnp.float32) * 0.02
          ).astype(jnp.bfloat16)
    wo = (jax.random.normal(k2, (E, I, H), jnp.float32) * 0.02
          ).astype(jnp.bfloat16)

    def block(mm):
        def f(xs, sizes, wi_, wo_):
            gu = mm(xs, wi_, sizes)
            h = (jax.nn.silu(gu[:, :I].astype(jnp.float32))
                 * gu[:, I:].astype(jnp.float32)).astype(jnp.bfloat16)
            return mm(h, wo_, sizes)
        return f

    def ragged(x, w, sizes):
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.bfloat16)

    def megablox(tiling):
        def mm(x, w, sizes):
            tm, tk, tn = tiling
            t = (tm, min(tk, w.shape[1]), min(tn, w.shape[2]))
            return gmm(x, w, sizes, preferred_element_type=jnp.bfloat16,
                       tiling=t)
        return mm

    rows_out = []
    for rows in (16, 512):
        m = rows * K
        rs = np.random.RandomState(rows)
        # the seeded router of random weights is near uniform: 8 distinct
        # experts a row
        choice = np.stack([rs.permutation(E)[:K] for _ in range(rows)])
        sizes_np = np.bincount(choice.reshape(-1), minlength=E).astype(np.int32)
        x = (jax.random.normal(k3, (m, H), jnp.float32)).astype(jnp.bfloat16)
        sizes = jnp.asarray(sizes_np)
        hit = int((sizes_np > 0).sum())
        flops = 2.0 * m * (H * 2 * I + I * H)
        nbytes = hit * (H * 2 * I + I * H) * 2 + 2 * m * H * 2
        least = max(flops / peak["bf16_flops_per_s"],
                    nbytes / peak["hbm_bytes_per_s"])
        # plain reference: a loop over experts on the host's slices
        ref = np.zeros((m, H), np.float32)
        start = 0
        xf = np.asarray(x, np.float32)
        for e in range(E):
            n = int(sizes_np[e])
            if n:
                gu = xf[start:start + n] @ np.asarray(wi[e], np.float32)
                h = (gu[:, :I] / (1 + np.exp(-gu[:, :I]))) * gu[:, I:]
                h = np.asarray(jnp.asarray(h).astype(jnp.bfloat16), np.float32)
                ref[start:start + n] = h @ np.asarray(wo[e], np.float32)
            start += n
        cands = [("ragged_dot", ragged)]
        for tiling in ((128, 128, 128), (128, 512, 512), (128, 1024, 1024),
                       (128, 2048, 512), (128, 2048, 1024), (128, 1024, 2048),
                       (128, 512, 2048), (256, 1024, 1024),
                       (512, 1024, 1024), (512, 2048, 512)):
            if m % tiling[0] == 0:
                cands.append((f"gmm{tiling}", megablox(tiling)))
        for name, mm in cands:
            try:
                fn = jax.jit(block(mm))
                out = fn(x, sizes, wi, wo)
                out.block_until_ready()
                err = float(np.max(np.abs(np.asarray(out, np.float32) - ref))
                            / (np.max(np.abs(ref)) + 1e-9))
                ts = []
                for _ in range(20):
                    t0 = time.perf_counter()
                    fn(x, sizes, wi, wo).block_until_ready()
                    ts.append(time.perf_counter() - t0)
                ms = float(np.median(ts) * 1e3)
                row = dict(rows=rows, m=m, experts_hit=hit, kernel=name,
                           ms=ms, least_ms=least * 1e3,
                           share_of_roofline=100 * least * 1e3 / ms,
                           rel_err=err)
            except Exception as e:  # a tiling the compiler refuses is a row
                row = dict(rows=rows, m=m, kernel=name,
                           error=f"{type(e).__name__}: {str(e)[:200]}")
            rows_out.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_gmm_probe.json", "w") as f:
        json.dump(rows_out, f, indent=1)


if __name__ == "__main__":
    main()
