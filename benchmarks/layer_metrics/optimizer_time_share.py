"""optimizer_time_share — self time of the operations under the train step's ``optimizer`` scope
(gradient clip and the fp32 Adam update) over the device's busy time.

BENCHMARK.json holds this metric's entries (``optimizer_time_share`` or ``optimizer_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "train loop"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    return None if sc is None else sc.share("optimizer")
