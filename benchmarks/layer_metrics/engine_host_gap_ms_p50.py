"""engine_host_gap_ms_p50 — median over the ``engine.step()`` calls of the
traced sub-window of the longest time the first chip ran no program inside
the call: how long the device waits for the host loop once a step (the
per-token round trip of ROADMAP S4).  The benchmark's own
``bench/engine_step`` spans mark the calls; the step's boundary on the
device does not sit at the span's edge (the call that fetches one step's
tokens launches the next step's first program), so the wait is found as the
span's longest gap between programs, not at its ends.

BENCHMARK.json holds this metric's entries (``engine_host_gap_ms_p50`` or ``engine_host_gap_ms_p50.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "ms"
SOURCE = "device_trace"

from benchmarks.harness import stats


def read(r):
    if r.trace is None:
        return None
    gaps = r.trace.span_gaps("engine_step")
    return None if not gaps else stats.median(gaps) * 1e3
