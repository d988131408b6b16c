"""Collective / memory micro-benchmarks (BASELINE.md's second named metric).

BASELINE.json names "TP all-reduce bandwidth (GB/s)" as a target metric; the
reference has no in-repo harness for it either (its collectives ride the
Neuron runtime; SURVEY §5.8).  This tool measures, on whatever devices are
visible:

- ``all_reduce``: ring-algorithm bus bandwidth of a psum over all devices,
  per message size.  Algorithm bandwidth uses the standard ring factor
  2*(n-1)/n so the number is comparable to NCCL-style busbw reports.  On a
  multi-chip mesh this exercises ICI; on the 8-device virtual CPU mesh it
  measures the host emulation (still useful as a regression canary for the
  collective code path).
- ``hbm_triad``: single-device HBM read+write bandwidth via an elementwise
  a*x+y (2 reads + 1 write per element), the memory-side calibration.
  Only this is physically meaningful when a single real chip is visible.

Prints one JSON line.
"""

from __future__ import annotations

import json
import time


def _timeit(fn, sync, iters: int = 10, warmup: int = 3) -> float:
    for _ in range(warmup):
        out = fn()
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    sync(out)
    return (time.perf_counter() - t0) / iters


def bench_all_reduce(devices) -> list[dict]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


    n = len(devices)
    mesh = Mesh(devices, ("x",))
    rows = []
    on_cpu = devices[0].platform == "cpu"
    sizes = (1, 4) if on_cpu else (1, 4, 16, 64, 256)
    for mib in sizes:
        nelem = mib * (1 << 20) // 2  # bf16
        x = jax.device_put(
            jnp.ones((n, nelem), jnp.bfloat16), NamedSharding(mesh, P("x", None))
        )

        @jax.jit
        def allreduce(x):
            return jax.shard_map(
                lambda s: jax.lax.psum(s, "x"),
                mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
                check_vma=False,
            )(x)

        try:
            dt = _timeit(lambda: allreduce(x), lambda o: o.block_until_ready())
        except Exception as e:  # noqa: BLE001 — report per-size failures
            rows.append({"size_mib": mib, "error": str(e)[:200]})
            continue
        bytes_ = nelem * 2
        busbw = (2 * (n - 1) / n) * bytes_ / dt if n > 1 else bytes_ / dt
        rows.append({
            "size_mib": mib,
            "time_us": round(dt * 1e6, 1),
            "busbw_gbps": round(busbw / 1e9, 2),
        })
    return rows


def bench_hbm_triad(device) -> list[dict]:
    import jax
    import jax.numpy as jnp

    rows = []
    sizes = (64, 256, 1024) if device.platform != "cpu" else (16, 64)
    for mib in sizes:
        nelem = mib * (1 << 20) // 4  # fp32
        x = jax.device_put(jnp.ones((nelem,), jnp.float32), device)
        y = jax.device_put(jnp.full((nelem,), 2.0, jnp.float32), device)

        @jax.jit
        def triad(x, y):
            return 1.5 * x + y

        dt = _timeit(lambda: triad(x, y), lambda o: o.block_until_ready())
        bytes_moved = 3 * nelem * 4  # 2 reads + 1 write
        rows.append({
            "size_mib": mib,
            "time_us": round(dt * 1e6, 1),
            "bw_gbps": round(bytes_moved / dt / 1e9, 2),
        })
    return rows


def main() -> int:
    import jax

    devices = jax.devices()
    kind = getattr(devices[0], "device_kind", devices[0].platform)
    result = {
        "metric": "collective_microbench",
        "device": kind,
        "n_devices": len(devices),
        "all_reduce": bench_all_reduce(devices),
        "hbm_triad": bench_hbm_triad(devices[0]),
        "note": (
            "all_reduce busbw is ICI-meaningful only when n_devices>1 on real "
            "chips; on one chip psum is a self-copy and hbm_triad is the "
            "physically meaningful row"
        ),
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
