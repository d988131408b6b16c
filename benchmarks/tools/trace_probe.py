#!/usr/bin/env python
"""trace_probe.py — record a SMALL profiler trace of the kernels the cells run
and print what is in it.

Not part of any cell.  It exists because the trace reduction
(``benchmarks/harness/trace_reduce.py``) has to be written against what the
chip's profiler really emits: which planes are devices, which lines hold the
operations, how a Mosaic call is named.  Run on the chip::

    chiprun -- python benchmarks/tools/trace_probe.py

It writes ``chiprun_out/trace_probe/probe.xplane.pb`` (the recorded trace the
yardstick's tests read, checked in as ``benchmarks/tests/data/``) and prints
one summary per plane and line.
"""

import collections
import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.ops.paged_attention import paged_attention
    from neuronx_distributed_tpu.ops.ring_attention import ring_attention

    print("devices:", jax.devices())
    nxd.initialize_model_parallel(tensor_parallel_size=1,
                                  devices=jax.devices()[:1])
    B, S, NQ, NKV, D = 1, 1024, 8, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(ks[0], (B, S, NQ, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, NKV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, NKV, D), jnp.bfloat16)
    w = jax.random.normal(ks[3], (NQ * D, NQ * D), jnp.bfloat16)

    def loss(q, k, v, w):
        o = ring_attention(q, k, v, causal=True, window=512)
        y = o.reshape(B, S, NQ * D) @ w
        return jnp.sum(y.astype(jnp.float32) ** 2)

    train = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))
    page, PP, NP_ = 16, 32, 80
    pool = (jax.random.normal(ks[4], (NP_, NKV, page, D), jnp.bfloat16),
            jax.random.normal(ks[5], (NP_, NKV, page, D), jnp.bfloat16))
    table = jnp.asarray(np.random.RandomState(0).randint(1, NP_, (4, PP)),
                        jnp.int32)
    off = jnp.asarray([100, 200, 300, 500], jnp.int32)
    qd = jax.random.normal(ks[6], (4, 1, NQ, D), jnp.bfloat16)
    decode = jax.jit(lambda q, pool, t, o: paged_attention(q, pool, t, o))

    for _ in range(2):  # compile + warm
        jax.block_until_ready(train(q, k, v, w))
        jax.block_until_ready(decode(qd, pool, table, off))

    out = os.path.join("chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    import time
    jax.profiler.start_trace(out)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench/step", step=i):
            with jax.profiler.TraceAnnotation("bench/train"):
                jax.block_until_ready(train(q, k, v, w))
            with jax.profiler.TraceAnnotation("bench/sleep"):
                time.sleep(0.005)
            with jax.profiler.TraceAnnotation("bench/decode"):
                jax.block_until_ready(decode(qd, pool, table, off))
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    final = os.path.join(out, "probe.xplane.pb")
    shutil.copy(path, final)
    shutil.rmtree(os.path.join(out, "plugins"))
    print("trace bytes:", os.path.getsize(final))
    data = jax.profiler.ProfileData.from_file(final)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            dur = collections.Counter()
            for e in evs:
                dur[e.name] += e.duration_ns
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for name, n in names.most_common(14):
                print(f"    {n:5d} x {name[:110]!r} total {dur[name]} ns")
            if evs:
                e = evs[len(evs) // 2]
                print(f"    sample: start_ns={e.start_ns} dur={e.duration_ns} "
                      f"stats={[(k, str(v)[:80]) for k, v in e.stats][:14]}")


if __name__ == "__main__":
    main()
