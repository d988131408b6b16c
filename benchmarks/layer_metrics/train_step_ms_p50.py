"""train_step_ms_p50 — median time between the stamps of consecutive steps
(each follows fit()'s fetch of that step's loss), whole window.

BENCHMARK.json holds this metric's entries (``train_step_ms_p50`` or ``train_step_ms_p50.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "train loop"
UNIT = "ms"
SOURCE = "host_clock"

from benchmarks.harness import stats


def read(r):
    return stats.median(r.samples.get("train_step_ms", []))
