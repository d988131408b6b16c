"""paged_window_roofline — over the paged-attention kernel calls of the WINDOW layers in the traced
window, the least time the chip could take (the larger of: the K and V rows
of the keys a call attends, at the pool's dtype, over the HBM bandwidth; its
QK^T and PV over the bf16 peak) summed, over their measured time summed.
The keys are ``window_tokens`` of the host span that launched the call's
program — each row's keys capped at the window, summed exactly a slot
(``harness/window_flops.py``); None for a program that writes no such key.

BENCHMARK.json holds this metric's entries (``paged_window_roofline`` or ``paged_window_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import window_flops


def read(r):
    return window_flops.roofline(r, "window")
