"""unscoped_time_share — self time of the device operations that no row of the table in
``harness/trace_scopes.py`` takes (group ``other``: what the ``[scopes]`` line
cannot name) over the device's busy time.

BENCHMARK.json holds this metric's entries (``unscoped_time_share`` or ``unscoped_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    return None if sc is None else sc.share("other")
