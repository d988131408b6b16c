"""queue_wait_p50_ms — median RequestOutput.queue_ms (submit to slot grant, the
scheduler's own span) over the requests that finished in the window.

BENCHMARK.json holds this metric's entries (``queue_wait_p50_ms`` or ``queue_wait_p50_ms.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "scheduler"
UNIT = "ms"
SOURCE = "program_span"

from benchmarks.harness import stats


def read(r):
    return stats.median(r.samples.get("queue_wait_ms", []))
