"""paged_time_share — time of the paged-attention Mosaic calls (HLO name
paged_attention*) over the device's busy time.

BENCHMARK.json holds this metric's entries (``paged_time_share`` or ``paged_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_reduce

PAGED = "paged_attention"


def is_paged(text):
    return (trace_reduce.is_mosaic(text)
            and trace_reduce.hlo_name(text).startswith(PAGED))


def read(r):
    if r.trace is None or not r.trace.busy_s():
        return None
    return 100.0 * r.trace.time_of(is_paged) / r.trace.busy_s()
