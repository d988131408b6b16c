"""collective_exposed_share — the part of the collective time during which no
other operation ran on that chip, over the device's busy time, mean over chips.

BENCHMARK.json holds this metric's entries (``collective_exposed_share`` or ``collective_exposed_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "collectives"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_reduce


def read(r):
    if r.trace is None or r.chips < 2 or not r.trace.busy_s():
        return None
    return (100.0 * r.trace.exposed_time_of(trace_reduce.is_collective)
            / r.trace.busy_s())
