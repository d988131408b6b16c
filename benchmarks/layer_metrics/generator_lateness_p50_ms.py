"""generator_lateness_p50_ms — median over requests due in the window of (time
submitted - time due), on the benchmark's clock.  A generator that runs late
makes the server look fast: TTFT is taken from the due time so the lateness
is inside it, and this metric says how much of it is the benchmark's own loop.

BENCHMARK.json holds this metric's entries (``generator_lateness_p50_ms`` or ``generator_lateness_p50_ms.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "entry"
UNIT = "ms"
SOURCE = "host_clock"

from benchmarks.harness import stats


def read(r):
    return stats.median(r.samples.get("lateness_ms", []))
