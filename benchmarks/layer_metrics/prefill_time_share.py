"""prefill_time_share — device time of the programs that the engine launched
from inside a ``nxd/serve/prefill_chunk`` span (found by their run ids) over the
device's busy time: what the decode steps of the window pay for the prompt
chunks riding with them.

BENCHMARK.json holds this metric's entries (``prefill_time_share`` or ``prefill_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.busy_s or not sc.steps():
        return None
    return 100.0 * sc.seconds_launched_under(
        trace_scopes.SERVE + "prefill_chunk") / sc.busy_s
