"""slots_active_mean — mean of the gauge serving/slots_active read after every
engine step of the window.

BENCHMARK.json holds this metric's entries (``slots_active_mean`` or ``slots_active_mean.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "scheduler"
UNIT = "count"
SOURCE = "program_counter"

from benchmarks.harness import stats


def read(r):
    return stats.mean(r.samples.get("slots_active", []))
