"""mlp_time_share — self time of the operations whose name stack passes through an ``mlp``
module (gate/up, activation, down; forward, recomputation and backward) over
the device's busy time (``harness/trace_scopes.py`` holds the table).

BENCHMARK.json holds this metric's entries (``mlp_time_share`` or ``mlp_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    return None if sc is None else sc.share("mlp")
