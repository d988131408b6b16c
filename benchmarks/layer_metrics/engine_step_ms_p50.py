"""engine_step_ms_p50 — median wall time of the benchmark's own calls to
ServingEngine.step() inside the window.

BENCHMARK.json holds this metric's entries (``engine_step_ms_p50`` or ``engine_step_ms_p50.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "ms"
SOURCE = "host_clock"

from benchmarks.harness import stats


def read(r):
    return stats.median(r.samples.get("engine_step_ms", []))
