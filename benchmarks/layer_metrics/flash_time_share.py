"""flash_time_share — time of the flash-attention Mosaic calls over the device's
busy time.  The flash kernels carry no name yet (they appear as
%branch_0_fun.N): every Mosaic call that is not the paged kernel is one.

BENCHMARK.json holds this metric's entries (``flash_time_share`` or ``flash_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_reduce

PAGED = "paged_attention"


def is_flash(text):
    return (trace_reduce.is_mosaic(text)
            and not trace_reduce.hlo_name(text).startswith(PAGED))


def read(r):
    if r.trace is None or not r.trace.busy_s():
        return None
    return 100.0 * r.trace.time_of(is_flash) / r.trace.busy_s()
