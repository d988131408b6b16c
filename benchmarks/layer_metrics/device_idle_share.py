"""device_idle_share — 1 - (union of the operation intervals on a chip / traced
window), mean over chips.

BENCHMARK.json holds this metric's entries (``device_idle_share`` or ``device_idle_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"

def read(r):
    return None if r.trace is None else 100.0 * r.trace.idle_share()
