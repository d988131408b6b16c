#!/usr/bin/env python3
"""benchmarks/run.py — one cell, one run, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, runner, reference and per-layer metric readers
are files found by name (``benchmarks/harness/manifest.py``).  One process,
no child process.  It fails, with no result line, where JAX finds no TPU,
a device kind that ``harness/peaks.json`` does not hold, or fewer chips
than the cell asks for.

The last line of standard output is the result::

    {"correct": ..., "attempted": n, "failed": n,
     "metrics": {name: {"value": v, "unit": u}, ...},
     "device": {"platform", "kind", "count", "memory_peak_bytes"
                [, "busy_s", "window_s"]} [, "breakdown": {...}]}

with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics and the breakdown of a short traced sub-window (written
under ``chiprun_out/benchmarks/<cell>/``).  Run notes — sample counts,
rates, losses, why a run is not correct — are on earlier lines.

``--rehearse`` runs the same control flow at the tiny sizes each file holds
under ``"rehearse"``, on whatever platform is there (the CPU, kernels
interpreted); its last line says ``"correct": false`` and ``"rehearsal":
true``: a rehearsal is not a result.  ``--sweep r1,r2,...`` (open-loop serve
cells) measures the mix at each arrival rate after one set-up and prints a
table, no result line: it is how a mix's knee is found, once.
"""

import time

_PROCESS_START = time.perf_counter()  # before the heavy imports: set-up time

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import common, manifest  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", type=lambda s: [float(x) for x in s.split(",")],
                    default=None)
    args = ap.parse_args()

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    if args.seconds is None:
        args.seconds = float(cell.manifest["run_seconds"])

    # the compile cache: a fixed directory inside the checkout, one per
    # cell (the chip machine caps a cache directory at 192 MiB and evicts
    # least-recently-used entries: cells sharing one would evict each
    # other).  The program's own helper takes JAX_COMPILATION_CACHE_DIR
    # where the machine sets it, and this directory where it does not.
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(manifest.REPO_ROOT, ".jax_cache", cell.name))
    from neuronx_distributed_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    import jax

    devices, peak = common.check_devices(cell, args.rehearse)
    dev = devices[0]
    common.log(f"[device] {len(jax.devices())} x {dev.device_kind} "
               f"({dev.platform}), cell {cell.name} uses {cell.chips}; "
               f"compile cache {cache_dir}; seed {args.seed}, window "
               f"{args.seconds} s, trace {args.trace}")

    clock = common.Clock(_PROCESS_START)
    out = cell.runner().run(cell, args, devices, peak, clock)
    for reason in out.why_not:
        common.log(f"[not correct] {reason}")

    reading = out.reading
    metrics = {}
    if args.trace:
        for entry in cell.per_layer:
            value = cell.layer_metric(entry["name"]).read(reading)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
    else:
        for entry in cell.end_to_end:
            value = (out.setup_s if entry["name"] == "setup_s"
                     else reading.end_to_end.get(entry["name"]))
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
    device = {"platform": dev.platform, "kind": str(dev.device_kind),
              "count": cell.chips,
              "memory_peak_bytes": out.memory.get("peak_bytes_in_use", 0)}
    result = {"correct": bool(out.correct) and not args.rehearse,
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if reading.trace is not None:
        if not args.rehearse and not reading.trace.busy_s() > 0:
            raise SystemExit("the traced sub-window holds no device "
                             "operation: no result")
        device["busy_s"] = reading.trace.busy_s()
        device["window_s"] = reading.trace.window_s
        result["breakdown"] = {"device_ops": reading.trace.top_ops(10),
                               "idle_gaps": reading.trace.idle_gaps(10)}
        common.log(f"[trace] window {device['window_s']:.3f} s, busy "
                   f"{device['busy_s']:.3f} s on {len(reading.trace.devices)}"
                   f" chip(s); e2e of this traced run (not a result): "
                   f"{reading.end_to_end}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
