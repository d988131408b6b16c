"""paged_full_roofline — over the paged-attention kernel calls of the GLOBAL layers in the traced
window, the least time the chip could take (the larger of: the K and V rows
of the keys a call attends, at the pool's dtype, over the HBM bandwidth; its
QK^T and PV over the bf16 peak) summed, over their measured time summed.
The keys are ``ctx_tokens`` of the host span that launched the call's
program — every key before the row: a global layer has no window
(``harness/window_flops.py``).

BENCHMARK.json holds this metric's entries (``paged_full_roofline`` or ``paged_full_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import window_flops


def read(r):
    return window_flops.roofline(r, "full")
