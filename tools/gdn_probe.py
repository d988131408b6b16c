#!/usr/bin/env python
"""gdn_probe.py — what ONE gated-delta layer's core costs alone on the chip
(``ops/gated_delta.py``): the chunk, its prologue alone, and the step's XLA
form beside its Pallas call.

    chiprun -- python tools/gdn_probe.py [--rows 8] [--chunk 512] [--steps 20]

At Qwen3-Next's sizes (32 value heads of 128 x 128, a state array of
``--rows`` rows of 2 MiB, donated): a prefill chunk of ``--chunk`` rows
continuing ONE row (``gdn_chunk``: the prologue of block operands and the
walk written out, XLA both), the prologue ALONE,
and a decode of ``LIVE`` of the rows (``gdn_step``: the XLA gather / step /
scatter and the Pallas step).  A line a variant: ``call_us`` (every device operation of the
call, the median of ``--steps`` calls' mean), ``kernel_us`` (the Mosaic
call's median, None for an XLA form), the share of the least time
(``benchmarks/harness/gdn_flops.py``) and how far output and state are from
the token recurrence.  ``--cpu --tiny`` rehearses every variant through the
interpreter at a toy shape (no number of it is a device number)."""

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def device_us(fn, steps, state, *xs):
    """``(kernel_us, call_us)`` of one call from a profiler trace of
    ``steps`` calls that hand the donated state on; ``(None, None)`` where
    there is no TPU to trace."""
    import jax

    y, state = jax.block_until_ready(fn(state, *xs))
    if jax.devices()[0].platform != "tpu":
        return None, None
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                y, state = fn(state, *xs)
            jax.block_until_ready(y)
        [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
    events = [(e.name.lstrip("%"), e.duration_ns)
              for plane in data.planes
              if plane.name.startswith("/device:TPU:")
              for line in plane.lines if line.name == "XLA Ops"
              for e in line.events]
    ours = [ns for name, ns in events if name.startswith("gdn_")]
    return (statistics.median(ours) / 1e3 if ours else None,
            sum(ns for _, ns in events) / 1e3 / steps)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--live", default="8,5,1")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import gdn_flops
    from benchmarks.harness.manifest import peaks_for
    from neuronx_distributed_tpu.ops import gated_delta as gd

    NH, D, R, S = (4, 16, 4, 24) if args.tiny else (32, 128, args.rows,
                                                    args.chunk)
    interp = True if args.cpu else None
    act = jnp.float32 if args.tiny else jnp.bfloat16
    cfg = {"linear_num_key_heads": NH // 2, "linear_num_value_heads": NH,
           "linear_key_head_dim": D, "linear_value_head_dim": D,
           "linear_conv_kernel_dim": 4}
    dev = jax.devices()[0]
    peak = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    def draw(B, T):
        q = gd.l2_normalise(jax.random.normal(ks[0], (B, T, NH, D))) * D ** -0.5
        k = gd.l2_normalise(jax.random.normal(ks[1], (B, T, NH, D)))
        v = jax.random.normal(ks[2], (B, T, NH, D)).astype(act)
        g = -jnp.exp(jax.random.uniform(ks[3], (B, T, NH), minval=-7.0,
                                        maxval=1.0))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, NH)))
        return q, k, v, g, beta

    fresh_state = lambda: jax.random.normal(  # noqa: E731
        ks[5], (R, NH, D, D), jnp.float32)
    out = []

    # -- a chunk continuing row 1 ---------------------------------------
    q, k, v, g, beta = draw(1, S)
    rows = jnp.array([1], jnp.int32)
    o_ref, s_ref = gd.gdn_reference(q, k, v, g, beta, None,
                                    fresh_state()[rows])
    fn = jax.jit(lambda st: gd.gdn_chunk(
        q, k, v, g, beta, None, jnp.zeros((1,), bool), st, rows),
        donate_argnums=0)
    o, st = fn(fresh_state())
    err = (float(jnp.max(jnp.abs(o - o_ref)) / jnp.max(jnp.abs(o_ref))),
           float(jnp.max(jnp.abs(st[1] - s_ref[0]))
                 / jnp.max(jnp.abs(s_ref))))
    _, call_us = device_us(fn, args.steps, fresh_state())
    least = (gdn_flops.chunk_least_seconds(S, cfg, peak)
             if peak else (None, None))
    out.append({"variant": "chunk", "rows": S, "call_us": call_us,
                "least_us": least[0] and least[0] * 1e6, "bound": least[1],
                "roofline_pct": (100.0 * least[0] * 1e6 / call_us
                                 if call_us else None),
                "o_err": err[0], "state_err": err[1]})
    print(json.dumps(out[-1]), flush=True)

    # the chunk's prologue ALONE (what is parallel over blocks: the decay
    # masks, the inverse, W, U, the scores)
    pro = jax.jit(lambda st: (jax.tree.map(
        lambda a: jnp.sum(a.astype(jnp.float32)),
        gd._prepare(q, k, v, g, beta, None, gd.CHUNK_ROWS)[2]), st))
    _, call_us = device_us(pro, args.steps, fresh_state())
    out.append({"variant": "chunk_prologue_alone", "rows": S,
                "call_us": call_us})
    print(json.dumps(out[-1]), flush=True)

    # -- a decode of LIVE of the rows -----------------------------------
    q, k, v, g, beta = (a[:, 0] for a in draw(R, 1))
    for live_n in [int(x) for x in args.live.split(",")]:
        live_n = min(live_n, R)
        live = np.zeros((R,), bool)
        live[np.random.RandomState(0).permutation(R)[:live_n]] = True
        live = jnp.asarray(live)
        o_ref, s_ref = gd.gdn_reference(
            q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
            live[:, None], fresh_state())
        for name, kern in (("step_xla", False), ("step_kernel", True)):
            fn = jax.jit(lambda st, kern=kern, live=live: gd.gdn_step(
                st, q, k, v, g, beta, live, None, None, kernel=kern,
                interpret=interp), donate_argnums=0)
            o, st = fn(fresh_state())
            m = live[:, None, None]
            err = (float(jnp.max(jnp.abs(jnp.where(m, o - o_ref[:, 0], 0.0)))
                         / jnp.max(jnp.abs(o_ref))),
                   float(jnp.max(jnp.abs(st - s_ref))
                         / jnp.max(jnp.abs(s_ref))))
            kernel_us, call_us = device_us(fn, args.steps, fresh_state())
            least = (gdn_flops.step_least_seconds(live_n, cfg, peak)
                     if peak else None)
            out.append({"variant": name, "live": live_n, "rows": R,
                        "kernel_us": kernel_us, "call_us": call_us,
                        "least_us": least and least * 1e6,
                        "roofline_pct": (100.0 * least * 1e6 / call_us
                                         if call_us and least else None),
                        "o_err": err[0], "state_err": err[1]})
            print(json.dumps(out[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gdn_probe.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
