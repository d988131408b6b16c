"""step_tail_idle_share — the first chip's idle seconds under the serve loop's
``nxd/serve/tail`` span (what a step does after ``finish``: the gauges with
the pool's walk of its evictable pages, the watchdog, the health rules, the
compile ledger's poll) as a percentage of the traced window:
``Scopes.idle_by_span``, the number the ``[phases]`` line prints as "device
idle under it".  The tail launches nothing, so the device waits under it
whenever the program queued before it ends first.  ``None`` where the window
holds no such span (a program older than the span).

BENCHMARK.json holds this metric's entries (``step_tail_idle_share`` or ``step_tail_idle_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

TAIL = trace_scopes.SERVE + "tail"


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.named(TAIL):
        return None
    lo, hi = sc.window
    return 100.0 * sc.idle_by_span().get(TAIL, 0.0) / (hi - lo)
