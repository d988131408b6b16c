"""flash_roofline — least time the chip could take for the flash kernels' work
(forward 2 and backward 5 matmuls over the attended keys; q, k, v, o and the
gradients moved once; benchmarks/harness/flops.py) over their measured time
per step.  The bound that sets it is printed on an earlier line.

BENCHMARK.json holds this metric's entries (``flash_roofline`` or ``flash_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import flops
from benchmarks.layer_metrics.flash_time_share import is_flash  # one pattern


def read(r):
    if r.trace is None or r.peak is None:
        return None
    runs = r.trace.dominant_runs()  # whole executions of the train step
    steps = len(runs)
    if steps < 1:
        return None
    kernel_s = r.trace.time_of(is_flash, (runs[0].start, runs[-1].end))
    if not kernel_s:
        return None
    cfg, n = r.cell.config, r.notes
    # a chip of a tp mesh runs its share of the heads
    least, bound = flops.roofline_seconds(
        flops.flash_train_flops(cfg, n["batch"], n["seq_len"]) / r.chips,
        flops.flash_train_bytes(cfg, n["batch"], n["seq_len"]) / r.chips,
        r.peak)
    print(f"[flash_roofline] {steps} traced steps, "
          f"{kernel_s / steps * 1e3:.2f} ms of flash kernels a step, least "
          f"{least * 1e3:.2f} ms ({bound} bound)", flush=True)
    return 100.0 * least / (kernel_s / steps)
