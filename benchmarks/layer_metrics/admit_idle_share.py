"""admit_idle_share — the first chip's idle seconds under the serve loop's
``nxd/serve/admit`` span (sweep, preemption, and per granted request: page
keys, prefix lookup, eviction, allocation, the block table, the validity
insert) as a percentage of the traced window: ``Scopes.idle_by_span``, the
number the ``[phases]`` line prints as "device idle under it".  What the
device waits for while the host admits; ``device_idle_share`` holds it and
every other wait together.  ``None`` where the window holds no such span (a
program older than the span, a cell that does not serve).

BENCHMARK.json holds this metric's entries (``admit_idle_share`` or ``admit_idle_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kv cache"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

ADMIT = trace_scopes.SERVE + "admit"


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.named(ADMIT):
        return None
    lo, hi = sc.window
    return 100.0 * sc.idle_by_span().get(ADMIT, 0.0) / (hi - lo)
